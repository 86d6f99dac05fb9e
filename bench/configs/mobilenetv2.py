"""mobilenetv2 as the system under test serves it:
``models/mobilenet_v2.py``'s graph with the configuration's weight scales,
requant shifts, join pre-shifts and ReLU6 caps pinned, lowered by
``compile_graph``."""

from __future__ import annotations

import numpy as np


def build(cfg: dict, weights: dict):
    """The compiled ``NetworkProgram``; every shift and cap is pinned and
    the graph is compiled against a zero image, so nothing but the
    configuration and the weights decides it."""
    from repro.graph import compile_graph
    from repro.models.mobilenet_v2 import build_mobilenet_v2

    size = cfg["input"]["shape"][-1]
    graph = build_mobilenet_v2(weights, cfg["weight_exps"],
                               width=cfg["width"], image_size=size)
    pinned = {"requant": cfg["requant_shifts"], "add": cfg["join_pre_shifts"],
              "relu6": cfg["relu6_caps"]}
    for node in graph.nodes.values():
        if node.kind not in pinned:
            continue
        if node.name not in pinned[node.kind]:
            raise ValueError(f"mobilenetv2 configuration pins no value for "
                             f"{node.kind} node {node.name!r}")
        value = pinned[node.kind][node.name]
        if node.kind == "requant":
            node.shift = value
        elif node.kind == "add":
            node.pre_shifts = tuple(value)
        else:
            node.cap = value
    zero = np.zeros(cfg["input"]["shape"], np.int8)
    return compile_graph(graph, zero, calib=[zero])
