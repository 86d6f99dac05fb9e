"""resnet8 as the system under test serves it: ``models/resnet8.py``'s
graph with the configuration's weight scales, requant shifts and join
pre-shifts pinned, lowered by ``compile_graph``."""

from __future__ import annotations

import numpy as np


def build(cfg: dict, weights: dict):
    """The compiled ``NetworkProgram``; every shift is pinned and the
    graph is compiled against a zero image, so nothing but the
    configuration and the weights decides it."""
    from repro.graph import compile_graph
    from repro.models.resnet8 import Resnet8Weights, build_resnet8

    fields = {}
    for name, (w, b) in weights.items():
        fields[f"{name}_w"], fields[f"{name}_b"] = w, b
    graph = build_resnet8(Resnet8Weights(**fields), cfg["weight_exps"])
    pinned = {"requant": cfg["requant_shifts"], "add": cfg["join_pre_shifts"]}
    for node in graph.nodes.values():
        if node.kind not in pinned:
            continue
        if node.name not in pinned[node.kind]:
            raise ValueError(f"resnet8 configuration pins no value for "
                             f"{node.kind} node {node.name!r}")
        if node.kind == "requant":
            node.shift = pinned["requant"][node.name]
        else:
            node.pre_shifts = tuple(pinned["add"][node.name])
    zero = np.zeros(cfg["input"]["shape"], np.int8)
    return compile_graph(graph, zero, calib=[zero])
