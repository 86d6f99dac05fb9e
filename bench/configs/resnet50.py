"""resnet50 as the system under test serves it: ``models/resnet50.py``'s
graph with the configuration's weight scales, requant shifts and join
pre-shifts pinned, lowered by ``compile_graph``."""

from __future__ import annotations

import numpy as np


def build(cfg: dict, weights: dict):
    """The compiled ``NetworkProgram``; every shift is pinned and the
    graph is compiled against a zero image, so nothing but the
    configuration and the weights decides it."""
    from repro.graph import compile_graph
    from repro.models.resnet50 import build_resnet50

    size = cfg["input"]["shape"][-1]
    graph = build_resnet50(weights, cfg["weight_exps"],
                           base_width=cfg["base_width"],
                           blocks=tuple(cfg["blocks"]), image_size=size)
    pinned = {"requant": cfg["requant_shifts"], "add": cfg["join_pre_shifts"]}
    for node in graph.nodes.values():
        if node.kind not in pinned:
            continue
        if node.name not in pinned[node.kind]:
            raise ValueError(f"resnet50 configuration pins no value for "
                             f"{node.kind} node {node.name!r}")
        if node.kind == "requant":
            node.shift = pinned["requant"][node.name]
        else:
            node.pre_shifts = tuple(pinned["add"][node.name])
    zero = np.zeros(cfg["input"]["shape"], np.int8)
    return compile_graph(graph, zero, calib=[zero])
