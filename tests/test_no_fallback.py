"""The pallas path runs on the chip or on the CPU test path, and nowhere
else: no silent interpret mode, no silent host fallback.

* ``ops.pallas_interpret`` interprets on ``cpu`` only; ``tpu`` compiles;
  any other platform, or interpret forced on a TPU, is a typed error —
  and serving inherits that refusal.
* ``chip_smoke.py`` refuses a device that is not a TPU.
* The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
  one fixed, git-ignored directory of the checkout.
"""

from pathlib import Path

import jax
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.core.errors import CompileError
from repro.kernels import ops
from repro.kernels.compile_cache import (ENV_VAR, REPO_CACHE_DIR,
                                         compile_cache_dir)

REPO = Path(__file__).resolve().parents[1]


def _platform(monkeypatch, name):
    monkeypatch.setattr(jax, "default_backend", lambda: name)


def test_interpret_on_cpu_compiled_on_tpu(monkeypatch):
    assert ops.pallas_interpret() is True          # the test platform
    _platform(monkeypatch, "tpu")
    assert ops.pallas_interpret() is False


@pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm", "METAL"])
def test_interpret_refuses_other_platforms(monkeypatch, platform):
    _platform(monkeypatch, platform)
    with pytest.raises(CompileError) as exc:
        ops.pallas_interpret()
    assert exc.value.constraint == "pallas-platform"


def test_interpret_refuses_forced_interpret_on_tpu(monkeypatch):
    _platform(monkeypatch, "tpu")
    with pltpu.force_tpu_interpret_mode():
        with pytest.raises(CompileError) as exc:
            ops.pallas_interpret()
    assert exc.value.constraint == "pallas-interpret-on-tpu"


def test_pallas_serve_refuses_without_chip(monkeypatch, chip_smoke):
    """The serving front door reaches the same decision: a process whose
    platform is neither tpu nor cpu gets an error, never bytes."""
    net, images, _ = chip_smoke.lenet5()
    _platform(monkeypatch, "gpu")
    with pytest.raises(CompileError) as exc:
        net.serve(images[:2], backend="pallas")
    assert exc.value.constraint == "pallas-platform"


def test_chip_smoke_refuses_the_cpu(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="only on a TPU"):
        chip_smoke.check_device(jax.devices())


def test_compile_cache_dir_follows_the_environment():
    assert compile_cache_dir({ENV_VAR: "/somewhere/cache"}) == \
        Path("/somewhere/cache")
    assert compile_cache_dir({}) == REPO_CACHE_DIR == REPO / ".jax_cache"
    assert compile_cache_dir({"HOME": "/elsewhere"}) == REPO_CACHE_DIR
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
