"""lenet5 as the system under test serves it: ``models/lenet.py``'s five
layer specs, compiled by ``compile_network`` with the configuration's
shifts and the weights the benchmark drew."""

from __future__ import annotations

import numpy as np

# configuration layer name -> LeNetWeights field prefix
_FIELDS = {"l1_conv": "conv1", "l2_conv": "conv2", "l3_conv": "conv3",
           "l4_fc": "fc4", "l5_fc": "fc5"}


def build(cfg: dict, weights: dict):
    """The compiled ``NetworkProgram``; compiled against a zero image, so
    nothing but the configuration and the weights decides it."""
    from repro.core.network_compiler import compile_network
    from repro.models.lenet import LeNetWeights, lenet5_specs

    fields = {}
    for name, prefix in _FIELDS.items():
        w, b = weights[name]
        fields[f"{prefix}_w"], fields[f"{prefix}_b"] = w, b
    shifts = [layer["shift"] for layer in cfg["layers"]]
    zero = np.zeros(cfg["input"]["shape"], np.int8)
    return compile_network(lenet5_specs(LeNetWeights(**fields), shifts), zero)
