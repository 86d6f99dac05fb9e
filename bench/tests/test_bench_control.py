"""The control: the plain reference at int4 in the program's place, at
the cell's traffic over a short window on the CPU.  It must come out not
correct on every seed, by the same numbers the runs compare."""

import io
import time

import pytest

from bench import control, harness, layout


@pytest.mark.parametrize("cell, seed", [
    ("lenet5.online", 1),
    ("lenet5.online", 2 ** 31 + 3),
    ("lenet5.online", -9),
    ("resnet8.offline", 2 ** 31 + 5),
    ("resnet8.offline", 11),
])
def test_int4_control_is_not_correct(cell, seed):
    done = harness.run_cell(layout.load_cell(cell), seed, 1.0,
                            False, t_start=time.monotonic(),
                            rehearsal=True, serve_with=control.int4_serve,
                            out=io.StringIO(), err=io.StringIO())
    result, _ = done
    assert result["correct"] is False
    assert result["checks"]["wrong_answers"]["value"] > 0
    assert result["checks"]["max_logit_diff"]["value"] > 0
