"""resnet50's plain reference: weights and images from a seed, the integer
forward pass, and the useful work per image (numpy only).

Each activation that the network stores is truncated to int8; inside a
fused step (a residual join, the global pool after the last one) values
stay wide, as the accelerator keeps them in its int32 accumulator.  The
harness runs it over the whole pool of request images after the window,
so images go through in chunks, in NHWC order, stored activations as
int8 and sums as int32 (every sum here is far below 2**31).  A conv or
fc multiplies in float32 BLAS where its bound ``max|x| · max|w| · K``
shows every sum below 2**24 (float32 then holds each one exactly), in
float64 where it is below 2**53, and refuses otherwise.
"""

from __future__ import annotations

import numpy as np

from bench import refops

CHUNK = 8            # images per pass: bounds the im2col buffers

weights = refops.draw_weights


def _magnitude(x: np.ndarray) -> int:
    return max(-int(x.min(initial=0)), int(x.max(initial=0)))


def _exact_dtype(x: np.ndarray, w: np.ndarray, k: int):
    bound = _magnitude(x) * _magnitude(w) * k
    if bound < 2 ** 24:
        return np.float32
    if bound < 2 ** 53:
        return np.float64
    raise ValueError(f"a dot product of {k} terms may reach {bound}, "
                     f"beyond what float64 holds exactly")


def conv(x: np.ndarray, w: np.ndarray, b: np.ndarray, *, stride: int,
         padding: int) -> np.ndarray:
    """Cross-correlation of ``x`` (B, H, W, C) with ``w`` (F, C, k, k)
    plus ``b`` (F,), zero padding; int32 (B, OH, OW, F)."""
    n, h, wd, c = x.shape
    f, _, k, _ = w.shape
    dt = _exact_dtype(x, w, c * k * k)
    oh = (h + 2 * padding - k) // stride + 1
    ow = (wd + 2 * padding - k) // stride + 1
    if k == 1 and padding == 0:
        cols = x[:, ::stride, ::stride, :].astype(dt)
    else:
        xp = np.pad(x, ((0, 0), (padding, padding), (padding, padding),
                        (0, 0)))
        cols = np.empty((n, oh, ow, k, k, c), dt)
        for i in range(k):
            for j in range(k):
                cols[:, :, :, i, j] = xp[:, i:i + stride * oh:stride,
                                         j:j + stride * ow:stride]
    wm = w.transpose(2, 3, 1, 0).reshape(k * k * c, f).astype(dt)
    acc = cols.reshape(n * oh * ow, k * k * c) @ wm
    return acc.astype(np.int32).reshape(n, oh, ow, f) + b.astype(np.int32)


def fc(x: np.ndarray, w: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``x`` (B, D) times ``w`` (D, F) plus ``b`` (F,); int32 (B, F)."""
    dt = _exact_dtype(x, w, x.shape[1])
    acc = x.astype(dt) @ w.astype(dt)
    return acc.astype(np.int32) + b.astype(np.int32)


def max3x3s2(x: np.ndarray) -> np.ndarray:
    """3×3/stride-2 max pool with padding 1 over (B, H, W, C): each
    window's maximum over its positions inside the map."""
    n, h, wd, c = x.shape
    oh, ow = (h - 1) // 2 + 1, (wd - 1) // 2 + 1
    xp = np.full((n, h + 2, wd + 2, c), np.iinfo(x.dtype).min, x.dtype)
    xp[:, 1:-1, 1:-1] = x
    out = xp[:, 0:2 * oh:2, 0:2 * ow:2].copy()
    for i in range(3):
        for j in range(3):
            np.maximum(out, xp[:, i:i + 2 * oh:2, j:j + 2 * ow:2], out=out)
    return out


def forward(cfg: dict, w: dict, images: np.ndarray,
            quant=refops.exact()) -> np.ndarray:
    """Logits (B, 1, 1000) int8 of ``images`` (B, 3, 224, 224); ``quant``
    as in :func:`bench.configs.lenet5_ref.forward`."""
    return np.concatenate([_forward(cfg, w, images[i:i + CHUNK], quant)
                           for i in range(0, len(images), CHUNK)])


def _forward(cfg, w, images, quant):
    act, wgt = quant
    q = cfg["requant_shifts"]
    pre = cfg["join_pre_shifts"]
    geo = {layer["name"]: layer for layer in cfg["layers"]}

    def t8(x):                  # two's-complement truncation to int8
        return x.astype(np.int8)

    def relu(x):
        return np.maximum(x, 0)

    def lin(name, x):
        wl, bl = w[name]
        layer = geo[name]
        return conv(act(x), wgt(wl.astype(np.int64)), bl,
                    stride=layer["stride"], padding=layer["padding"])

    x = np.ascontiguousarray(images.transpose(0, 2, 3, 1))
    x = t8(max3x3s2(relu(lin("stem", x))) >> q["stem_q"])
    blocks = [layer["name"][:-1] for layer in cfg["layers"]
              if layer["name"].endswith("a")]
    for i, name in enumerate(blocks):
        a = t8(relu(lin(f"{name}a", x)) >> q[f"{name}a_q"])
        skip = (t8(lin(f"{name}p", x) >> q[f"{name}p_q"])
                if f"{name}p" in geo else x)
        b = t8(relu(lin(f"{name}b", a)) >> q[f"{name}b_q"])
        pb, ps = pre[f"{name}_join"]
        s = relu(((lin(f"{name}c", b) >> q[f"{name}c_q"]) >> pb)
                 + (skip.astype(np.int32) >> ps))
        if i == len(blocks) - 1:
            s = s.sum(axis=(1, 2), keepdims=True)   # its ÷49 is in the shift
        x = t8(s >> q[f"{name}_q"])
    wf, bf = w["fc"]
    x = t8(fc(act(x.reshape(len(x), -1)), wgt(wf.astype(np.int64)), bf)
           >> q["fc_q"])
    return x.reshape(len(x), 1, -1)


def gemm_shapes(cfg: dict):
    """``[(layer, M, K, N)]`` of one image's GEMMs at their valid shapes,
    M counted before any pool."""
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
