"""mobilenetv2's plain reference: weights and images from a seed, the
integer forward pass, and the useful work per image (numpy only).

Each activation that the network stores is truncated to int8; inside a
fused step (a join, the global pool after the last ReLU6) values stay
wide, as the accelerator keeps them in its int32 accumulator.  Images go
through in chunks, in NHWC order, as ``resnet50_ref``'s do, sums as
int32 (every sum here is far below 2**31).  A dense conv or the fc
multiplies in float32 BLAS where its bound ``max|x| · max|w| · K`` shows
every sum below 2**24, in float64 where it is below 2**53, and refuses
otherwise; a depthwise conv is nine shifted multiply-adds in int32 where
its bound shows every sum below 2**31, and refuses otherwise.
"""

from __future__ import annotations

import numpy as np

from bench import refops

CHUNK = 16           # images per pass: bounds the expanded activations


def weights(cfg: dict, seed: int) -> dict:
    """``{layer name: (int8 weights, int32 bias)}`` drawn from ``seed``,
    uniform over the configuration's inclusive ranges, layer by layer in
    its order: a conv's weights are (out, in/groups, k, k) — (C, 1, 3, 3)
    for a depthwise conv — and the fc's (in, out)."""
    rng = refops.stream(seed, 0)
    r = cfg["weights"]
    out = {}
    for layer in cfg["layers"]:
        if layer["kind"] == "conv":
            shape = (layer["out"], layer["in"] // layer["groups"],
                     layer["kernel"], layer["kernel"])
        else:
            shape = (layer["in"], layer["out"])
        w = rng.integers(r["low"], r["high"], shape, endpoint=True)
        b = rng.integers(r["bias_low"], r["bias_high"], (layer["out"],),
                         endpoint=True)
        out[layer["name"]] = (w.astype(np.int8), b.astype(np.int32))
    return out


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


def pointwise(x: np.ndarray, w: np.ndarray, b: np.ndarray, *,
              stride: int = 1) -> np.ndarray:
    """1×1 conv of ``x`` (B, H, W, C) with ``w`` (F, C, 1, 1) plus ``b``
    (F,); int32 (B, OH, OW, F)."""
    x = x[:, ::stride, ::stride]
    n, h, wd, c = x.shape
    dt = _exact_dtype(x, w, c)
    acc = x.reshape(-1, c).astype(dt) @ w[:, :, 0, 0].T.astype(dt)
    return acc.astype(np.int32).reshape(n, h, wd, -1) + b.astype(np.int32)


def dense3x3(x: np.ndarray, w: np.ndarray, b: np.ndarray, *,
             stride: int) -> np.ndarray:
    """3×3 conv, padding 1 (the stem) of ``x`` (B, H, W, C) with ``w``
    (F, C, 3, 3) plus ``b``; int32 (B, OH, OW, F)."""
    n, h, wd, c = x.shape
    oh, ow = (h - 1) // stride + 1, (wd - 1) // stride + 1
    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    dt = _exact_dtype(x, w, 9 * c)
    cols = np.empty((n, oh, ow, 3, 3, c), dt)
    for i in range(3):
        for j in range(3):
            cols[:, :, :, i, j] = xp[:, i:i + stride * oh:stride,
                                     j:j + stride * ow:stride]
    wm = w.transpose(2, 3, 1, 0).reshape(9 * c, -1).astype(dt)
    acc = cols.reshape(n * oh * ow, 9 * c) @ wm
    return acc.astype(np.int32).reshape(n, oh, ow, -1) + b.astype(np.int32)


def depthwise(x: np.ndarray, w: np.ndarray, b: np.ndarray, *,
              stride: int) -> np.ndarray:
    """Depthwise 3×3 conv, padding 1, of ``x`` (B, H, W, C) with ``w``
    (C, 1, 3, 3) plus ``b`` (C,): channel c with its own kernel; int32
    (B, OH, OW, C)."""
    n, h, wd, c = x.shape
    if _magnitude(x) * _magnitude(w) * 9 + _magnitude(b) >= 2 ** 31:
        raise ValueError("a depthwise sum may pass 2**31")
    oh, ow = (h - 1) // stride + 1, (wd - 1) // stride + 1
    xp = np.pad(x.astype(np.int32), ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = np.zeros((n, oh, ow, c), np.int32)
    for i in range(3):
        for j in range(3):
            acc += xp[:, i:i + stride * oh:stride, j:j + stride * ow:stride] \
                * w[:, 0, i, j].astype(np.int32)
    return acc + b.astype(np.int32)


def fc(x: np.ndarray, w: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``x`` (B, D) times ``w`` (D, F) plus ``b`` (F,); int32 (B, F)."""
    dt = _exact_dtype(x, w, x.shape[1])
    acc = x.astype(dt) @ w.astype(dt)
    return acc.astype(np.int32) + b.astype(np.int32)


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
    caps = cfg["relu6_caps"]

    def t8(x):                  # two's-complement truncation to int8
        return x.astype(np.int8)

    def lin(layer, x):
        wl, bl = w[layer["name"]]
        wl = wgt(wl.astype(np.int64))
        x = act(x.astype(np.int32))
        if layer["groups"] > 1:
            return depthwise(x, wl, bl, stride=layer["stride"])
        if layer["kernel"] == 3:
            return dense3x3(x, wl, bl, stride=layer["stride"])
        return pointwise(x, wl, bl, stride=layer["stride"])

    x = np.ascontiguousarray(images.transpose(0, 2, 3, 1))
    block_in = None
    for layer in cfg["layers"][:-1]:
        name = layer["name"]
        if name.endswith(("ex", "dw")) and block_in is None:
            block_in = x
        y = lin(layer, x) >> q[f"{name}_q"]
        if layer["relu6"]:
            y = np.minimum(np.maximum(y, 0), caps[f"{name}_r6"])
        if layer["join"]:
            block = name[:-2]
            pb, ps = pre[f"{block}_join"]
            y = ((y >> pb) + (block_in.astype(np.int32) >> ps)) \
                >> q[f"{block}_q"]
        if name.endswith("pw") or name == "stem":
            block_in = None
        if name == "head":      # its ÷49 is in the shift
            y = y.sum(axis=(1, 2), keepdims=True) >> q["gap_q"]
        x = t8(y)
    wf, bf = w["fc"]
    x = t8(fc(act(x.reshape(len(x), -1).astype(np.int32)),
              wgt(wf.astype(np.int64)), bf) >> q["fc_q"])
    return x.reshape(len(x), 1, -1)


def _out(layer: dict) -> int:
    return (layer["in_size"] + 2 * layer["padding"] - layer["kernel"]) \
        // layer["stride"] + 1


def gemm_shapes(cfg: dict):
    """``[(layer, M, K, N)]`` of one image's GEMMs at their valid shapes;
    a depthwise conv as (layer, M, 9, C): its useful work, each channel
    with its own nine taps, and none of its band matrix's zeros."""
    shapes = []
    for layer in cfg["layers"]:
        if layer["kind"] == "fc":
            shapes.append((layer["name"], 1, layer["in"], layer["out"]))
            continue
        k = layer["kernel"] ** 2
        m = _out(layer) ** 2
        if layer["groups"] > 1:
            shapes.append((layer["name"], m, k, layer["out"]))
        else:
            shapes.append((layer["name"], m, layer["in"] * k,
                           layer["out"]))
    return shapes


def dw_shapes(cfg: dict):
    """``[(layer, H, W, C, M)]`` of one image's depthwise convs: the input
    map, its channels and the output positions."""
    return [(layer["name"], layer["in_size"], layer["in_size"], layer["in"],
             _out(layer) ** 2)
            for layer in cfg["layers"]
            if layer["kind"] == "conv" and layer["groups"] > 1]
