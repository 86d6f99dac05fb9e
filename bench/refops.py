"""Plain integer operations for the configurations' references.

numpy only, and nothing of the program: every configuration's reference
(``configs/<name>_ref.py``) is written with these, so that what decides
``correct`` shares no code with the system under test.  Activations are
int64 arrays of shape ``(batch, C, H, W)`` or ``(batch, D)``.
"""

from __future__ import annotations

import numpy as np

# float64 holds every integer below 2**53 exactly, so a product of int8
# activations and weights summed in float64 is exact while the sum stays
# below it; conv/fc check the bound before they rely on it.
_EXACT = 2 ** 53


def stream(seed: int, purpose: int) -> np.random.Generator:
    """An independent generator for one use of a run's ``--seed``
    (0 weights, 1 request images, 2 arrivals, 3 the order of requests);
    any whole number is a seed, negative ones included."""
    return np.random.default_rng([purpose, seed % 2 ** 64])


def draw_weights(cfg: dict, seed: int) -> dict:
    """``{layer name: (int8 weights, int32 bias)}`` drawn from ``seed``,
    uniform over the configuration's inclusive ranges, layer by layer in
    its order; conv weights are (out, in, k, k), fc weights (in, out)."""
    rng = stream(seed, 0)
    r = cfg["weights"]
    out = {}
    for layer in cfg["layers"]:
        if layer["kind"] == "conv":
            shape = (layer["out"], layer["in"], layer["kernel"],
                     layer["kernel"])
        else:
            shape = (layer["in"], layer["out"])
        w = rng.integers(r["low"], r["high"], shape, endpoint=True)
        b = rng.integers(r["bias_low"], r["bias_high"], (layer["out"],),
                         endpoint=True)
        out[layer["name"]] = (w.astype(np.int8), b.astype(np.int32))
    return out


def draw_images(cfg: dict, seed: int, n: int) -> np.ndarray:
    """``n`` request images (n, C, H, W) int8 from ``seed``, uniform over
    the configuration's input range [low, high)."""
    spec = cfg["input"]
    shape = (n,) + tuple(spec["shape"][1:])
    return stream(seed, 1).integers(spec["low"], spec["high"],
                                    shape).astype(np.int8)


def trunc8(x: np.ndarray) -> np.ndarray:
    """Two's-complement truncation to int8: the low 8 bits, signed."""
    return ((x + 128) & 255) - 128


def _check_exact(x: np.ndarray, w: np.ndarray, k: int) -> None:
    bound = int(np.abs(x).max(initial=0)) * int(np.abs(w).max(initial=0)) * k
    if bound >= _EXACT:
        raise ValueError(f"a dot product of {k} terms may reach {bound}, "
                         f"beyond what float64 holds exactly")


def conv(x: np.ndarray, w: np.ndarray, b: np.ndarray, *, stride: int = 1,
         padding: int = 0) -> np.ndarray:
    """Cross-correlation of ``x`` (B, C, H, W) with ``w`` (F, C, k, k)
    plus bias ``b`` (F,), zero padding; int64 (B, F, OH, OW)."""
    n, c, h, wd = x.shape
    f, _, k, _ = w.shape
    xp = np.pad(x, ((0, 0), (0, 0), (padding, padding), (padding, padding)))
    oh = (h + 2 * padding - k) // stride + 1
    ow = (wd + 2 * padding - k) // stride + 1
    cols = np.empty((n, oh, ow, c, k, k), np.float64)
    for i in range(k):
        for j in range(k):
            win = xp[:, :, i:i + stride * oh:stride, j:j + stride * ow:stride]
            cols[:, :, :, :, i, j] = win.transpose(0, 2, 3, 1)
    _check_exact(x, w, c * k * k)
    acc = cols.reshape(n * oh * ow, c * k * k) @ \
        w.reshape(f, -1).T.astype(np.float64)
    out = acc.reshape(n, oh, ow, f).transpose(0, 3, 1, 2).astype(np.int64)
    return out + b.astype(np.int64)[None, :, None, None]


def fc(x: np.ndarray, w: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``x`` (B, D) times ``w`` (D, F) plus ``b`` (F,); int64 (B, F)."""
    _check_exact(x, w, x.shape[1])
    acc = x.astype(np.float64) @ w.astype(np.float64)
    return acc.astype(np.int64) + b.astype(np.int64)[None, :]


def relu(x: np.ndarray) -> np.ndarray:
    return np.maximum(x, 0)


def pool_sum(x: np.ndarray) -> np.ndarray:
    """Sum over 2x2 windows at stride 2 (the average's division is the
    requant shift's business)."""
    return (x[:, :, 0::2, 0::2] + x[:, :, 0::2, 1::2]
            + x[:, :, 1::2, 0::2] + x[:, :, 1::2, 1::2])


def global_sum(x: np.ndarray) -> np.ndarray:
    """Sum over all positions: (B, C, H, W) -> (B, C, 1, 1)."""
    return x.sum(axis=(2, 3), keepdims=True)


def drop_low_bits(x: np.ndarray, bits: int) -> np.ndarray:
    """``x`` with its ``bits`` lowest bits cleared (floor to a multiple
    of ``2**bits``): the value a coarser integer grid keeps."""
    return (x >> bits) << bits


def low_precision(bits: int):
    """Quantizers for a reference computed at ``bits`` in place of int8,
    the control of the comparison: activations keep their top ``bits``
    bits, weights the fewest low bits dropped that fit ``bits`` signed.
    Returns ``(act, wgt)``, each a function of an int64 array."""
    def act(x):
        return drop_low_bits(x, 8 - bits)

    def wgt(w):
        need = int(np.abs(w).max(initial=0)).bit_length() + 1
        return drop_low_bits(w.astype(np.int64), max(0, need - bits))
    return act, wgt


def exact():
    """The identity quantizers: the reference at the stated precision."""
    same = lambda x: x  # noqa: E731
    return same, same
