"""resnet8's plain reference: weights and images from a seed, the integer
forward pass, and the useful work per image (numpy only).

Each activation that the network stores is truncated to int8; inside a
fused step (a residual join, the global pool) values stay wide, as the
accelerator keeps them in its int32 accumulator.
"""

from __future__ import annotations

import numpy as np

from bench import refops

weights = refops.draw_weights


def forward(cfg: dict, w: dict, images: np.ndarray,
            quant=refops.exact()) -> np.ndarray:
    """Logits (B, 1, 10) int8 of ``images`` (B, 3, 32, 32); ``quant`` as
    in :func:`bench.configs.lenet5_ref.forward`."""
    act, wgt = quant
    q = cfg["requant_shifts"]
    pre = cfg["join_pre_shifts"]
    geo = {layer["name"]: layer for layer in cfg["layers"]}
    t8 = refops.trunc8
    relu = refops.relu

    def lin(name, x):
        wl, bl = w[name]
        layer = geo[name]
        return refops.conv(act(x), wgt(wl.astype(np.int64)), bl,
                           stride=layer["stride"], padding=layer["padding"])

    def identity_block(name, x):
        a = t8(relu(lin(f"{name}a", x)) >> q[f"{name}a_q"])
        pb, ps = pre[f"{name}_join"]
        s = ((lin(f"{name}b", a) >> q[f"{name}b_q"]) >> pb) + (x >> ps)
        return t8(relu(s) >> q[f"{name}_q"])

    def downsample_block(name, x):
        a = t8(relu(lin(f"{name}a", x)) >> q[f"{name}a_q"])
        p = t8(lin(f"{name}p", x) >> q[f"{name}p_q"])
        pb, ps = pre[f"{name}_join"]
        s = ((lin(f"{name}b", a) >> q[f"{name}b_q"]) >> pb) + (p >> ps)
        return t8(relu(s) >> q[f"{name}_q"])

    x = images.astype(np.int64)
    x = t8(relu(lin("stem", x)) >> q["stem_q"])
    x = identity_block("b1", x)
    x = downsample_block("t2", x)
    x = downsample_block("t3", x)
    x = t8(refops.global_sum(relu(lin("head", x))) >> q["head_q"])
    wf, bf = w["fc"]
    x = t8(refops.fc(act(x.reshape(len(x), -1)), wgt(wf.astype(np.int64)),
                     bf) >> q["fc_q"])
    return x.reshape(len(x), 1, -1).astype(np.int8)


def gemm_shapes(cfg: dict):
    """``[(layer, M, K, N)]`` of one image's GEMMs at their valid shapes,
    M counted before the global pool."""
    shapes = []
    for layer in cfg["layers"]:
        if layer["kind"] == "fc":
            shapes.append((layer["name"], 1, layer["in"], layer["out"]))
            continue
        k, s, p = layer["kernel"], layer["stride"], layer["padding"]
        out = (layer["in_size"] + 2 * p - k) // s + 1
        shapes.append((layer["name"], out * out, layer["in"] * k * k,
                       layer["out"]))
    return shapes
