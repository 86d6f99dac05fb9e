"""Compile the serving path's ``vta_gemm`` calls for a TPU v5e, no chip
attached.

Every kernel call ``NetworkProgram.serve(backend="pallas")`` makes for
lenet5 and resnet8 at batch-ladder rungs 1, 4 and 8 is compiled with
``interpret=False`` for a described ``v5e:2x2`` topology, both as the
program serving runs (``ops._padded_vta_gemm``: pads, ``vta_gemm``, slice)
and as the bare padded ``vta_gemm``: what the chip's compiler would refuse
(a misaligned block, too much VMEM) fails here at no chip time.  The
shapes come from ``plan_pallas`` → ``kernel_call``;
``test_rehearsal_calls_are_what_serving_runs`` pins that serving makes
exactly those calls.  The topology is described inside a
fixture, never at import, so every test worker collects the same tests.

ResNet-50's calls at the benchmark's rungs 1 and 8 are compiled too, as
the program serving runs; they are derived from its layer shapes and the
configuration's shifts (:func:`_resnet50_calls`), so that the whole net
is never compiled on the CPU, and the derivation is checked against the
calls a small compiled ResNet-50 makes.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core.pallas_backend import KernelCall, kernel_call, plan_pallas
from repro.kernels import ops
from repro.kernels.vta_gemm import vta_gemm

NETS = ("lenet5", "resnet8")
RUNGS = (1, 4, 8)           # the ladder rungs chip_smoke.py serves at


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def nets(chip_smoke):
    """The nets ``chip_smoke.py`` serves: (net, 16 request images)."""
    return {name: getattr(chip_smoke, name)()[:2] for name in NETS}


def _kernel_calls(net, rung):
    """The distinct kernel programs a pallas serve of ``rung`` images
    runs: ``{(operand shapes, static kwargs)}``."""
    calls = set()
    for layer in net.layers:
        args, statics = kernel_call(plan_pallas(layer.program),
                                    rung).matmul_args()
        shapes = tuple(a.shape if a is not None else None for a in args)
        calls.add((shapes, frozenset(statics.items())))
    return calls


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("net_name", NETS)
def test_vta_gemm_compiles_for_v5e(net_name, rung, nets, one_chip):
    net, _ = nets[net_name]
    calls = {kernel_call(plan_pallas(layer.program), rung)
             for layer in net.layers}
    for call in calls:
        args, statics = call.vta_gemm_args(sharding=one_chip)
        compiled = vta_gemm.lower(*args, interpret=False,
                                  **statics).compile()
        assert "tpu_custom_call" in compiled.as_text(), call


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("net_name", NETS)
def test_padded_vta_gemm_compiles_for_v5e(net_name, rung, nets, one_chip):
    """The program serving dispatches, pads and slice included."""
    net, _ = nets[net_name]
    calls = {kernel_call(plan_pallas(layer.program), rung)
             for layer in net.layers}
    for call in calls:
        args, statics = call.matmul_args(sharding=one_chip)
        compiled = ops._padded_vta_gemm.lower(*args, interpret=False,
                                              **statics).compile()
        assert "tpu_custom_call" in compiled.as_text(), call


def test_padded_vta_gemm_keeps_the_kernels_names(nets, one_chip):
    """The benchmark finds the kernel's time by ``vta_gemm`` in the module
    name and by the op ``%vta_gemm.N = ... custom-call``: the program that
    holds the pads and the slice must still read both."""
    net, _ = nets["lenet5"]
    args, statics = kernel_call(plan_pallas(net.layers[0].program),
                                1).matmul_args(sharding=one_chip)
    text = ops._padded_vta_gemm.lower(*args, interpret=False, **statics) \
        .compile().as_text()
    module, = re.findall(r"^HloModule (\S+),", text, re.M)
    assert "vta_gemm" in module, module
    assert re.search(r"%vta_gemm\.\d+ = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", text)


def test_vta_gemm_keeps_its_names_for_the_trace(nets, one_chip):
    """The benchmark finds the kernel in a chip trace by its module
    (``jit_vta_gemm``) and its op (``%vta_gemm.N = ... custom-call``); the
    ``name`` the ``pallas_call`` carries must leave both as they read."""
    net, _ = nets["lenet5"]
    args, statics = kernel_call(plan_pallas(net.layers[0].program),
                                1).vta_gemm_args(sharding=one_chip)
    text = vta_gemm.lower(*args, interpret=False, **statics) \
        .compile().as_text()
    assert re.search(r"^HloModule jit_vta_gemm,", text, re.M)
    assert re.search(r"%vta_gemm\.\d+ = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", text)


@pytest.mark.parametrize("net_name", NETS)
def test_rehearsal_calls_are_what_serving_runs(net_name, nets, monkeypatch):
    """Record every kernel call of real pallas serves (interpreted on the
    CPU) at the jitted program's boundary, where a repeat call still shows,
    and compare with what the compile tests above compile."""
    net, images = nets[net_name]
    seen = set()
    real = ops._padded_vta_gemm

    def spy(a, b, bias, **kw):
        kw.pop("interpret")
        seen.add((tuple(None if x is None else x.shape for x in (a, b, bias)),
                  frozenset(kw.items())))
        return real(a, b, bias, interpret=True, **kw)

    monkeypatch.setattr(ops, "_padded_vta_gemm", spy)
    for rung in RUNGS:
        net.serve(images[:rung], backend="pallas")
    assert seen == set().union(*(_kernel_calls(net, r) for r in RUNGS))


def _round_up(x, m):
    return -(-x // m) * m


def _resnet50_calls(layers, shifts, rung, bs=16):
    """The kernel calls a pallas serve of ``rung`` images runs for a
    ResNet-50 of ``layers`` (``models/resnet50.py linear_layers``) with
    the planned ``shifts``, from the shapes alone: the stem's rows are the
    padded 3×3/s2 max pool's bands, its pool and the joins (``c``) finish
    in the epilogue on an int32 GEMM, every other layer runs whole in the
    kernel (bias, ReLU on ``a``/``b``, its requant shift)."""
    calls = set()
    for layer in layers:
        name = layer["name"]
        if layer["kind"] == "fc":
            m, k, n = 1, layer["in"], layer["out"]
        else:
            kk, s, p = layer["kernel"], layer["stride"], layer["padding"]
            out = (layer["in_size"] + 2 * p - kk) // s + 1
            m, k, n = out * out, layer["in"] * kk * kk, layer["out"]
        if name == "stem":
            m = ((out - 1) // 2 + 1) * _round_up(3 * out, bs)
        shape = dict(m=rung * (_round_up(m, bs) if m > 1 else 1),
                     k=_round_up(k, bs), n=_round_up(n, bs))
        if name == "stem" or (layer["kind"] == "conv"
                              and name.endswith("c")):
            calls.add(KernelCall(**shape, bias=False, relu=False, shift=0,
                                 out_dtype=jnp.int32))
        else:
            calls.add(KernelCall(**shape, bias=True,
                                 relu=name.endswith(("a", "b")),
                                 shift=shifts[f"{name}_q"],
                                 out_dtype=jnp.int8))
    return calls


@pytest.mark.parametrize("rung", (1, 8))
def test_resnet50_calls_from_shapes_are_what_the_program_compiles(rung):
    from repro.models import resnet50 as r50
    geometry = dict(base_width=16, blocks=(1, 2, 1, 1), image_size=64)
    net, graph = r50.compile_resnet50(**geometry)
    assert _resnet50_calls(r50.linear_layers(**geometry),
                           r50.graph_shifts(graph), rung) == {
        kernel_call(plan_pallas(layer.program), rung)
        for layer in net.layers}


@pytest.mark.parametrize("rung", (1, 8))
def test_resnet50_padded_vta_gemm_compiles_for_v5e(rung, one_chip):
    cfg = json.loads((Path(__file__).resolve().parents[1] / "bench" /
                      "configs" / "resnet50.json").read_text())
    calls = _resnet50_calls(cfg["layers"], cfg["requant_shifts"], rung)
    for call in calls:
        args, statics = call.matmul_args(sharding=one_chip)
        compiled = ops._padded_vta_gemm.lower(*args, interpret=False,
                                              **statics).compile()
        assert "tpu_custom_call" in compiled.as_text(), call
