"""lenet5's plain reference: weights and images from a seed, the integer
forward pass, and the useful work per image (numpy only)."""

from __future__ import annotations

import numpy as np

from bench import refops

weights = refops.draw_weights


def forward(cfg: dict, w: dict, images: np.ndarray,
            quant=refops.exact()) -> np.ndarray:
    """Logits (B, 1, 10) int8 of ``images`` (B, 1, 32, 32).  ``quant``
    is ``(act, wgt)``: what each layer's input and weights pass through
    (the identity for the reference, :func:`refops.low_precision` for the
    control)."""
    act, wgt = quant
    x = images.astype(np.int64)
    for layer in cfg["layers"]:
        wl, bl = w[layer["name"]]
        wl = wgt(wl.astype(np.int64))
        if layer["kind"] == "conv":
            acc = refops.conv(act(x), wl, bl)
        else:
            acc = refops.fc(act(x.reshape(len(x), -1)), wl, bl)
        if layer["relu"]:
            acc = refops.relu(acc)
        shift = layer["shift"]
        if layer.get("pool") == "avg2x2":
            acc, shift = refops.pool_sum(acc), shift + 2
        x = refops.trunc8(acc >> shift)
    return x.reshape(len(x), 1, -1).astype(np.int8)


def gemm_shapes(cfg: dict):
    """``[(layer, M, K, N)]`` of one image's GEMMs at their valid shapes,
    M counted before pooling."""
    _, c, h, _ = cfg["input"]["shape"]
    shapes = []
    for layer in cfg["layers"]:
        if layer["kind"] == "conv":
            k = layer["kernel"]
            h = h - k + 1
            shapes.append((layer["name"], h * h, layer["in"] * k * k,
                           layer["out"]))
            if layer.get("pool"):
                h //= 2
        else:
            shapes.append((layer["name"], 1, layer["in"], layer["out"]))
    return shapes
