"""The kernel-call legs: ``ops.vta_matmul``'s pallas leg and
``ops.staged_vta_matmul``, which ``NetworkProgram.serve(backend="pallas")``
runs.  Both go through the one jitted program ``ops._staged_vta_gemm``
(``vta_matmul`` with no staging: one row per input row).

Its staging, pads, ``vta_gemm`` and slice run as one program, so a call
crosses into the runtime once.  Here, on the CPU (interpret mode), the
``vta_matmul`` leg is checked bit for bit against the ``xla`` reference at
the GEMM shape of every kernel call ``NetworkProgram.serve`` makes for
lenet5 and resnet8 at rungs 1, 4 and 8, in the fused int8 form (bias,
ReLU, shift, truncating commit) and the bare int32 form, and the jaxpr of
each leg is checked to be one jitted call holding one ``pallas_call``.
"""

import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pallas_backend import kernel_call
from repro.kernels import ops

NETS = ("lenet5", "resnet8")
RUNGS = (1, 4, 8)
FORMS = {
    "int8": dict(relu=True, saturate=False, out_dtype=jnp.int8),
    "int32": dict(relu=False, shift=0, saturate=False, out_dtype=jnp.int32),
}


@pytest.fixture(scope="module")
def nets(chip_smoke):
    return {name: getattr(chip_smoke, name)()[0] for name in NETS}


def _operands(rng, call, with_bias):
    a = rng.integers(-128, 128, (call.m, call.k), dtype=np.int8)
    w = rng.integers(-128, 128, (call.k, call.n), dtype=np.int8)
    bias = (rng.integers(-2**20, 2**20, call.n, dtype=np.int32)
            if with_bias else None)
    return a, w, bias


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("net_name", NETS)
def test_pallas_leg_is_bit_exact_against_xla(net_name, rung, form, nets):
    calls = {kernel_call(layer, rung)
             for layer in nets[net_name].layers}
    rng = np.random.default_rng(rung)
    for call in sorted(calls, key=repr):
        kw = dict(FORMS[form])
        if form == "int8":
            kw["shift"] = call.shift or 7
        a, w, bias = _operands(rng, call, with_bias=form == "int8")
        got = ops.vta_matmul(a, w, bias, backend="pallas", **kw)
        want = ops.vta_matmul(a, w, bias, backend="xla", **kw)
        assert got.shape == (call.m, call.n) and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=repr(call))


def _count(jaxpr, primitive):
    """Equations of ``primitive`` in ``jaxpr`` and the jaxprs it nests,
    not looking inside a match."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            n += 1
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jex.ClosedJaxpr):
                    n += _count(sub.jaxpr, primitive)
                elif isinstance(sub, jex.Jaxpr):
                    n += _count(sub, primitive)
    return n


@pytest.mark.parametrize("with_bias", (True, False))
def test_pallas_leg_is_one_jitted_call_with_one_pallas_call(with_bias):
    """Pads and slice inside the kernel's program, not dispatched around it,
    and a module name that still holds ``vta_gemm`` for the trace."""
    a = jnp.zeros((40, 25), jnp.int8)
    w = jnp.zeros((25, 6), jnp.int8)
    bias = jnp.zeros((6,), jnp.int32) if with_bias else None
    closed = jax.make_jaxpr(
        lambda a, w, bias: ops.vta_matmul(a, w, bias, relu=True, shift=3,
                                          backend="pallas"))(a, w, bias)
    eqn, = closed.jaxpr.eqns
    assert eqn.primitive.name in ("jit", "pjit"), eqn.primitive
    assert "vta_gemm" in eqn.params["name"], eqn.params["name"]
    assert _count(closed.jaxpr, "pallas_call") == 1
    assert eqn.outvars[0].aval.shape == (40, 6)


@pytest.mark.parametrize("conv", ((3, 3, 2, 1), None))
def test_staged_call_is_one_jitted_call_with_one_pallas_call(conv):
    """A staged from the activations inside the kernel's program, and a
    module name that still holds ``vta_gemm`` for the trace."""
    x = jnp.zeros((2, 4, 9, 9) if conv else (2, 40), jnp.int8)
    rows, cols = (32, 48) if conv else (1, 48)
    w = jnp.zeros((cols, 6), jnp.int8)
    bias = jnp.zeros((6,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda x, w, bias: ops.staged_vta_matmul(
            x, None, w, bias, conv=conv, rows=rows, cols=cols, relu=True,
            shift=3))(x, w, bias)
    eqn, = closed.jaxpr.eqns
    assert eqn.primitive.name in ("jit", "pjit"), eqn.primitive
    assert "vta_gemm" in eqn.params["name"], eqn.params["name"]
    assert _count(closed.jaxpr, "pallas_call") == 1
    assert eqn.outvars[0].aval.shape == (2 * rows, 6)
