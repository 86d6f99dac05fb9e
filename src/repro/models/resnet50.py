"""resnet50 — ImageNet ResNet-50 v1.5 at 224×224 through the graph IR.

He et al., "Deep Residual Learning for Image Recognition" (arXiv:1512.03385),
Table 1, the 50-layer column, in its v1.5 form (stride 2 on the 3×3 conv of
each stage's first bottleneck, as MLPerf Inference's image classification
runs it):

  stem    conv 3→64 k7 s2 p3 + ReLU, max pool 3×3 s2 p1   224 → 112 → 56
  stage 1 3 bottlenecks, width 64,  out 256               56×56
  stage 2 4 bottlenecks, width 128, out 512               28×28
  stage 3 6 bottlenecks, width 256, out 1024              14×14
  stage 4 3 bottlenecks, width 512, out 2048              7×7
  head    global average pool over 7×7, fc 2048→1000

A bottleneck is conv 1×1 + ReLU, conv 3×3 (stride s) + ReLU, conv 1×1
(×4 channels), joined with the block input — or, on each stage's first
block, with a 1×1/s projection of it — then ReLU.  54 GEMMs and
4,089,184,256 MACs per image at the published shapes.

What the lowering meets here for the first time (DESIGN.md
§Strided-lowering): the padded 3×3/s2 max pool after the stem, 1×1/s2
projections (floor-grid strided kernels narrower than their stride), and
the last join feeding a global pool over 7×7 = 49 positions.

Departures from He et al., all of integer inference:

* BatchNorm folded into each conv's weights and bias (the drawn weights
  stand for the folded ones);
* per-tensor power-of-two scales: int8 weights and activations, int32
  accumulation, a static arithmetic right shift after each layer and
  two's-complement truncation to int8;
* the global pool is a sum; its ÷49 is the following requant's shift
  (÷32) and the remaining factor 49/32 the next layer's scale.

:func:`reference_forward` is the plain float32 ``jax.numpy`` forward pass of
those integer semantics, with no kernels, DRAM image or batching; the
keyword arguments of :func:`build_resnet50` exist only so that the CPU
tests can build a small one.
"""

from __future__ import annotations

import re
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from repro.graph import Graph, GraphBuilder, compile_graph

BLOCKS = (3, 4, 6, 3)           # bottlenecks per stage (Table 1)
EXPANSION = 4                   # a bottleneck's output is 4× its width
NUM_CLASSES = 1000

Weights = Dict[str, Tuple[np.ndarray, np.ndarray]]
Shifts = Mapping[str, Union[int, Tuple[int, int]]]


def linear_layers(*, base_width: int = 64, blocks: Sequence[int] = BLOCKS,
                  image_size: int = 224,
                  num_classes: int = NUM_CLASSES) -> List[dict]:
    """Every conv/fc of the net in graph order: ``name``, ``kind``,
    ``in_size`` (input map side), ``in``, ``out``, ``kernel``,
    ``stride``, ``padding`` (conv) — the geometry the graph, the weight
    drawer and the reference share."""
    def conv(name, size, cin, cout, k, s, p):
        return {"name": name, "kind": "conv", "in_size": size, "in": cin,
                "out": cout, "kernel": k, "stride": s, "padding": p}

    size = (image_size + 2 * 3 - 7) // 2 + 1
    layers = [conv("stem", image_size, 3, base_width, 7, 2, 3)]
    size = (size - 1) // 2 + 1                      # the stem's max pool
    cin = base_width
    for si, n in enumerate(blocks):
        width = base_width * 2 ** si
        cout = width * EXPANSION
        for bi in range(n):
            name = f"s{si + 1}b{bi + 1}"
            stride = 2 if si > 0 and bi == 0 else 1
            out_size = (size - 1) // stride + 1
            layers.append(conv(f"{name}a", size, cin, width, 1, 1, 0))
            if bi == 0:
                layers.append(conv(f"{name}p", size, cin, cout, 1, stride,
                                   0))
            layers.append(conv(f"{name}b", size, width, width, 3, stride, 1))
            layers.append(conv(f"{name}c", out_size, width, cout, 1, 1, 0))
            size, cin = out_size, cout
    layers.append({"name": "fc", "kind": "fc", "in": cin,
                   "out": num_classes})
    return layers


def resnet50_random_weights(seed: int = 0, *, scale: int = 5,
                            **geometry) -> Weights:
    """Seeded int8 weights in ``[-scale, scale]`` and int32 biases in
    ``[-64, 64]`` for every layer of :func:`linear_layers` (conv weights
    (F, C, k, k), fc weights (D, F)).  Static power-of-two requant keeps
    every activation healthy over such weights, as for resnet8."""
    rng = np.random.default_rng(seed)
    out: Weights = {}
    for layer in linear_layers(**geometry):
        if layer["kind"] == "conv":
            shape = (layer["out"], layer["in"], layer["kernel"],
                     layer["kernel"])
        else:
            shape = (layer["in"], layer["out"])
        w = rng.integers(-scale, scale + 1, shape, dtype=np.int64)
        b = rng.integers(-64, 65, (layer["out"],), dtype=np.int64)
        out[layer["name"]] = (w.astype(np.int8), b.astype(np.int32))
    return out


def build_resnet50(weights: Weights,
                   weight_exps: Optional[Dict[str, int]] = None, *,
                   base_width: int = 64, blocks: Sequence[int] = BLOCKS,
                   image_size: int = 224) -> Graph:
    """The ResNet-50 v1.5 DAG (unplanned requants).  ``weights`` maps each
    layer of :func:`linear_layers` to ``(int8 weights, int32 bias)``;
    ``weight_exps`` maps it to the fixed-point scale of its weights."""
    geo = {layer["name"]: layer for layer in linear_layers(
        base_width=base_width, blocks=blocks, image_size=image_size,
        num_classes=weights["fc"][0].shape[1])}
    bld = GraphBuilder("resnet50")

    def conv(name: str, x: str) -> str:
        w, b = weights[name]
        layer = geo[name]
        return bld.conv(name, x, w, b, stride=layer["stride"],
                        padding=layer["padding"],
                        weight_exp=(weight_exps or {}).get(name, 0))

    v = bld.input("image", shape=(1, 3, image_size, image_size))
    v = bld.relu("stem_r", conv("stem", v))
    v = bld.requant("stem_q", bld.pool("stem_pool", v, "max3x3s2"))
    for si, n in enumerate(blocks):
        for bi in range(n):
            name = f"s{si + 1}b{bi + 1}"
            last = si == len(blocks) - 1 and bi == n - 1
            a = bld.requant(f"{name}a_q",
                            bld.relu(f"{name}a_r", conv(f"{name}a", v)))
            skip = (bld.requant(f"{name}p_q", conv(f"{name}p", v))
                    if bi == 0 else v)
            b = bld.requant(f"{name}b_q",
                            bld.relu(f"{name}b_r", conv(f"{name}b", a)))
            c = bld.requant(f"{name}c_q", conv(f"{name}c", b))
            v = bld.relu(f"{name}_r", bld.add(f"{name}_join", c, skip))
            if last:
                v = bld.global_avg_pool("gap", v)
            v = bld.requant(f"{name}_q", v)
    v = bld.flatten("flat", v)
    w, b = weights["fc"]
    v = bld.fc("fc", v, w, b, weight_exp=(weight_exps or {}).get("fc", 0))
    bld.output(bld.requant("fc_q", v))
    return bld.build()


def graph_shifts(graph: Graph) -> Dict[str, Union[int, Tuple[int, int]]]:
    """The planned shifts of a graph: requant node → shift, add node →
    (branch, skip) pre-shifts — what :func:`reference_forward` takes."""
    out: Dict[str, Union[int, Tuple[int, int]]] = {}
    for node in graph.nodes.values():
        if node.kind == "requant":
            out[node.name] = node.shift
        elif node.kind == "add":
            out[node.name] = tuple(node.pre_shifts)
    return out


def synthetic_image(seed: int = 0, image_size: int = 224) -> np.ndarray:
    """A deterministic 3×S×S int8 test image (centred dynamic range)."""
    rng = np.random.default_rng(seed)
    return rng.integers(-64, 64, (1, 3, image_size, image_size),
                        dtype=np.int64).astype(np.int8)


def calibrated_graph(weights: Weights, calib: Sequence[np.ndarray], *,
                     margin: int = 1, **geometry) -> Graph:
    """The graph with every shift planned over ``calib``.  Two-phase
    calibration as resnet8's: each linear node's weight scale from a
    throwaway plan, then the requant and join pre-shifts over the final
    graph, so :func:`repro.graph.evaluate_graph` on it is the bit-exact
    integer reference of the network compiled from it."""
    from repro.graph import plan_requant
    from repro.quantize.ptq import calibrate_integer_weight_exps
    names = [layer["name"] for layer in linear_layers(**geometry)]
    wexps = calibrate_integer_weight_exps(
        lambda: build_resnet50(weights, **geometry), calib, names,
        margin=margin)
    graph = build_resnet50(weights, wexps, **geometry)
    plan_requant(graph, list(calib), margin=margin)
    return graph


def compile_resnet50(weights: Optional[Weights] = None, *,
                     calib_seeds: Sequence[int] = range(1, 3),
                     input_seed: int = 0, margin: int = 1,
                     **geometry):
    """Build, calibrate (:func:`calibrated_graph`, over the images of
    ``calib_seeds`` and ``input_seed``) and compile; returns
    ``(net, graph)``."""
    size = geometry.get("image_size", 224)
    weights = weights or resnet50_random_weights(**geometry)
    image = synthetic_image(input_seed, size)
    calib = [synthetic_image(s, size) for s in calib_seeds] + [image]
    graph = calibrated_graph(weights, calib, margin=margin, **geometry)
    return compile_graph(graph, image, calib=calib, margin=margin), graph


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

_EXACT = 2 ** 24      # float32 holds every integer below it exactly


def reference_forward(weights: Weights, images: np.ndarray,
                      shifts: Shifts) -> np.ndarray:
    """Logits ``(B, 1, classes)`` int8 of int8 ``images`` ``(B, 3, S, S)``.

    Plain ``jax.numpy`` in float32 under ``default_matmul_precision(
    "highest")``: every value is an integer, every dot product is checked
    to stay below 2^24 so that float32 holds it exactly, shifts are
    floor divisions by powers of two and the int8 truncation is a
    two's-complement wrap — the integer semantics of ``bench/refops.py``.
    ``shifts`` is :func:`graph_shifts` of the planned graph."""
    import jax
    import jax.numpy as jnp

    geo = {layer["name"]: layer for layer in _layers_of(weights, images)}
    names = _block_names(geo)

    def check(x, w, k):
        bound = float(jnp.max(jnp.abs(x))) * float(np.abs(
            w.astype(np.int64)).max()) * k
        if bound >= _EXACT:
            raise ValueError(f"a dot product of {k} terms may reach "
                             f"{bound:.0f}, beyond what float32 holds")

    def conv(name, x):
        w, b = weights[name]
        layer = geo[name]
        check(x, w, w.shape[1] * w.shape[2] * w.shape[3])
        p = layer["padding"]
        y = jax.lax.conv_general_dilated(
            x, jnp.asarray(w, jnp.float32), (layer["stride"],) * 2,
            ((p, p), (p, p)), dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return y + jnp.asarray(b, jnp.float32)[None, :, None, None]

    def shr(x, s):
        return jnp.floor(x / 2.0 ** s)

    def int8(x):
        return jnp.mod(x + 128.0, 256.0) - 128.0

    def relu(x):
        return jnp.maximum(x, 0.0)

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(images, jnp.float32)
        x = relu(conv("stem", x))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                                  (1, 1, 2, 2),
                                  ((0, 0), (0, 0), (1, 1), (1, 1)))
        x = int8(shr(x, shifts["stem_q"]))
        for name, first, last in names:
            a = int8(shr(relu(conv(f"{name}a", x)), shifts[f"{name}a_q"]))
            skip = (int8(shr(conv(f"{name}p", x), shifts[f"{name}p_q"]))
                    if first else x)
            b = int8(shr(relu(conv(f"{name}b", a)), shifts[f"{name}b_q"]))
            # the join adds in the accumulator: the branch is not narrowed
            c = shr(conv(f"{name}c", b), shifts[f"{name}c_q"])
            pa, pb = shifts[f"{name}_join"]
            x = relu(shr(c, pa) + shr(skip, pb))
            if last:
                x = jnp.sum(x, axis=(2, 3), keepdims=True)
            x = int8(shr(x, shifts[f"{name}_q"]))
        x = x.reshape(x.shape[0], -1)
        w, b = weights["fc"]
        check(x, w, w.shape[0])
        x = x @ jnp.asarray(w, jnp.float32) + jnp.asarray(b, jnp.float32)
        x = int8(shr(x, shifts["fc_q"]))
    return np.asarray(x).astype(np.int8).reshape(len(images), 1, -1)


def _layers_of(weights: Weights, images: np.ndarray) -> List[dict]:
    """:func:`linear_layers` of the geometry the weights and images have."""
    firsts = [name for name in weights if re.fullmatch(r"s\d+b\d+a", name)]
    stages = sorted({int(name[1:].split("b")[0]) for name in firsts})
    blocks = tuple(sum(1 for name in firsts if name.startswith(f"s{s}b"))
                   for s in stages)
    return linear_layers(base_width=weights["stem"][0].shape[0],
                         blocks=blocks, image_size=images.shape[-1],
                         num_classes=weights["fc"][0].shape[1])


def _block_names(geo: Mapping[str, dict]) -> List[Tuple[str, bool, bool]]:
    """``(block name, has a projection, is the last block)`` in order."""
    blocks = [name[:-1] for name in geo if name.endswith("a")]
    return [(name, f"{name}p" in geo, i == len(blocks) - 1)
            for i, name in enumerate(blocks)]
