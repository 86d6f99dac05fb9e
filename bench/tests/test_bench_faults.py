"""Drive whole runs on the CPU (the look for a chip skipped, kernels
interpreted) with the timed path sound and then broken underneath, and
see ``correct`` follow: true when sound; false when an answer is altered
where the network produces it, and when half of a batch is left out and
its rows are answered from the rest."""

import io
import json
import time

import numpy as np
import pytest

from bench import harness


def _run(cell, serve_with=None, seed=2 ** 31 + 17):
    out = io.StringIO()
    done = harness.run(cell, seed, 1.0, False, t_start=time.monotonic(),
                       rehearsal=True, serve_with=serve_with, out=out,
                       err=io.StringIO())
    assert done == 0
    return json.loads(out.getvalue().splitlines()[-1])


def altered_answer(cell, weights):
    def serve(net, images, backend):
        outs, reports = net.serve(images, backend=backend)
        outs = outs.copy()
        outs[0, 0, 0] ^= 1               # one logit of the first request
        return outs, reports
    return serve


def half_batch_left_out(cell, weights):
    def serve(net, images, backend):
        keep = max(1, len(images) // 2)
        outs, reports = net.serve(images[:keep], backend=backend)
        fill = np.resize(outs, (len(images) - keep,) + outs.shape[1:])
        return np.concatenate([outs, fill]), reports
    return serve


def test_sound_run_is_correct():
    result = _run("lenet5.online")
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("cell, fault", [
    ("lenet5.online", altered_answer),
    ("lenet5.online", half_batch_left_out),
    ("resnet8.offline", half_batch_left_out),
])
def test_broken_path_is_not_correct(cell, fault):
    result = _run(cell, fault)
    assert result["correct"] is False
    assert result["checks"]["wrong_answers"]["value"] > 0
