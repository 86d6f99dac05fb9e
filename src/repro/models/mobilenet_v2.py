"""mobilenet_v2 — MobileNetV2 1.0 at 224×224 through the graph IR.

Sandler et al., "MobileNetV2: Inverted Residuals and Linear Bottlenecks"
(arXiv:1801.04381), Table 2:

  stem    conv 3→32 k3 s2 p1 + ReLU6                      224 → 112
  17 inverted residuals (t, c, n, s) = (1,16,1,1), (6,24,2,2),
          (6,32,3,2), (6,64,4,2), (6,96,3,1), (6,160,3,2), (6,320,1,1)
  head    conv 320→1280 k1 + ReLU6, global average pool over 7×7,
          fc 1280→1000

An inverted residual is a 1×1 expansion to t× its input's channels with
ReLU6 (none where t = 1), a depthwise 3×3 (stride s on the first of its n
repeats) with ReLU6, and a linear 1×1 projection; where the stride is 1 and
the channels stay, the block's input joins the projection with no ReLU
after it (the linear bottleneck).  53 GEMMs, 17 of them depthwise,
300,774,272 MACs and 3,469,760 weights per image at the published shapes;
35 ReLU6 and 10 joins.

What the lowering meets here for the first time (DESIGN.md §Graph): the
depthwise conv (``groups`` = channels) as a grouped GEMM, ReLU6 as MAX 0
and MIN cap after the requant, joins with no ReLU, and a global pool after
a ReLU6 rather than after a join.

Departures from Sandler et al., all of integer inference:

* BatchNorm folded into each conv's weights and bias (the drawn weights
  stand for the folded ones);
* per-tensor power-of-two scales: int8 weights and activations, int32
  accumulation, a static arithmetic right shift after each layer and
  two's-complement truncation to int8 (deployed int8 MobileNetV2 scales
  its depthwise weights per channel);
* ReLU6 is min(max(x, 0), 6·2^e) on the requantised value, 2^e its scale;
* the global pool is a sum; its ÷49 is the following requant's shift
  (÷32) and the remaining factor 49/32 the fc's scale;
* no dropout (inference).

:func:`reference_forward` is the plain float32 ``jax.numpy`` forward pass of
those integer semantics; the ``width`` and ``image_size`` keywords exist
only so that the CPU tests can build a small net.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from repro.graph import Graph, GraphBuilder, compile_graph

# (expansion t, output channels c, repeats n, first stride s), Table 2
BLOCKS = ((1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
          (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1))
STEM = 32
LAST = 1280
NUM_CLASSES = 1000

Weights = Dict[str, Tuple[np.ndarray, np.ndarray]]
Params = Mapping[str, Union[int, Tuple[int, int]]]


def _divisible(v: float, d: int = 8) -> int:
    """Channels under a width multiplier, as the reference
    implementations round them: to a multiple of 8, never below 90% of
    ``v``."""
    out = max(d, int(v + d / 2) // d * d)
    return out + d if out < 0.9 * v else out


def linear_layers(*, width: float = 1.0, image_size: int = 224,
                  num_classes: int = NUM_CLASSES) -> List[dict]:
    """Every conv/fc of the net in graph order: ``name``, ``kind``,
    ``in_size`` (input map side), ``in``, ``out``, ``kernel``,
    ``stride``, ``padding``, ``groups`` (conv; ``groups`` = ``in`` for a
    depthwise conv), ``relu6`` and ``join`` (the block's input joins this
    projection) — the geometry the graph, the weight drawer and the
    references share."""
    def conv(name, size, cin, cout, k, s, groups=1, relu6=True,
             join=False):
        return {"name": name, "kind": "conv", "in_size": size, "in": cin,
                "out": cout, "kernel": k, "stride": s, "padding": k // 2,
                "groups": groups, "relu6": relu6, "join": join}

    cin = _divisible(STEM * width)
    layers = [conv("stem", image_size, 3, cin, 3, 2)]
    size = (image_size - 1) // 2 + 1
    k = 0
    for t, c, n, s in BLOCKS:
        cout = _divisible(c * width)
        for i in range(n):
            k += 1
            stride = s if i == 0 else 1
            hidden = cin * t
            if t != 1:
                layers.append(conv(f"b{k}ex", size, cin, hidden, 1, 1))
            layers.append(conv(f"b{k}dw", size, hidden, hidden, 3, stride,
                               groups=hidden))
            size = (size - 1) // stride + 1
            layers.append(conv(f"b{k}pw", size, hidden, cout, 1, 1,
                               relu6=False,
                               join=stride == 1 and cin == cout))
            cin = cout
    last = _divisible(LAST * max(1.0, width))
    layers.append(conv("head", size, cin, last, 1, 1))
    layers.append({"name": "fc", "kind": "fc", "in": last,
                   "out": num_classes})
    return layers


def mobilenet_v2_random_weights(seed: int = 0, *, scale: int = 5,
                                **geometry) -> Weights:
    """Seeded int8 weights in ``[-scale, scale]`` and int32 biases in
    ``[-64, 64]`` for every layer of :func:`linear_layers` (conv weights
    (F, C/groups, k, k), fc weights (D, F))."""
    rng = np.random.default_rng(seed)
    out: Weights = {}
    for layer in linear_layers(**geometry):
        if layer["kind"] == "conv":
            shape = (layer["out"], layer["in"] // layer["groups"],
                     layer["kernel"], layer["kernel"])
        else:
            shape = (layer["in"], layer["out"])
        w = rng.integers(-scale, scale + 1, shape, dtype=np.int64)
        b = rng.integers(-64, 65, (layer["out"],), dtype=np.int64)
        out[layer["name"]] = (w.astype(np.int8), b.astype(np.int32))
    return out


def build_mobilenet_v2(weights: Weights,
                       weight_exps: Optional[Dict[str, int]] = None, *,
                       width: float = 1.0, image_size: int = 224) -> Graph:
    """The MobileNetV2 DAG (unplanned requants and caps).  ``weights``
    maps each layer of :func:`linear_layers` to ``(int8 weights, int32
    bias)``; ``weight_exps`` maps it to the fixed-point scale of its
    weights."""
    layers = linear_layers(width=width, image_size=image_size,
                           num_classes=weights["fc"][0].shape[1])
    bld = GraphBuilder("mobilenet_v2")
    exps = weight_exps or {}
    v = bld.input("image", shape=(1, 3, image_size, image_size))
    block_in = None
    for layer in layers[:-1]:
        name = layer["name"]
        w, b = weights[name]
        if name.endswith(("ex", "dw")) and block_in is None:
            block_in = v
        y = bld.requant(f"{name}_q", bld.conv(
            name, v, w, b, stride=layer["stride"],
            padding=layer["padding"], groups=layer["groups"],
            weight_exp=exps.get(name, 0)))
        if layer["relu6"]:
            y = bld.relu6(f"{name}_r6", y)
        if layer["join"]:
            block = name[:-2]
            y = bld.requant(f"{block}_q",
                            bld.add(f"{block}_join", y, block_in))
        if name.endswith("pw") or name == "stem":
            block_in = None
        v = y
    v = bld.requant("gap_q", bld.global_avg_pool("gap", v))
    w, b = weights["fc"]
    v = bld.fc("fc", bld.flatten("flat", v), w, b,
               weight_exp=exps.get("fc", 0))
    bld.output(bld.requant("fc_q", v))
    return bld.build()


def graph_params(graph: Graph) -> Dict[str, Union[int, Tuple[int, int]]]:
    """The planned values of a graph: requant node → shift, add node →
    (branch, skip) pre-shifts, relu6 node → cap — what
    :func:`reference_forward` takes."""
    out: Dict[str, Union[int, Tuple[int, int]]] = {}
    for node in graph.nodes.values():
        if node.kind == "requant":
            out[node.name] = node.shift
        elif node.kind == "add":
            out[node.name] = tuple(node.pre_shifts)
        elif node.kind == "relu6":
            out[node.name] = node.cap
    return out


def synthetic_image(seed: int = 0, image_size: int = 224) -> np.ndarray:
    """A deterministic 3×S×S int8 test image (centred dynamic range)."""
    rng = np.random.default_rng(seed)
    return rng.integers(-64, 64, (1, 3, image_size, image_size),
                        dtype=np.int64).astype(np.int8)


def calibrated_graph(weights: Weights, calib: Sequence[np.ndarray], *,
                     margin: int = 1, **geometry) -> Graph:
    """The graph with every shift and cap planned over ``calib``.
    Two-phase calibration as resnet50's: each linear node's weight scale
    from a throwaway plan, then the requants, the join pre-shifts and the
    ReLU6 caps over the final graph (the planner puts each cap inside
    int8 and below the largest value its layer gives, and sets that
    layer's weight scale to the cap's), so :func:`repro.graph.
    evaluate_graph` on it is the bit-exact integer reference of the
    network compiled from it."""
    from repro.graph import plan_requant
    from repro.quantize.ptq import calibrate_integer_weight_exps
    names = [layer["name"] for layer in linear_layers(**geometry)]
    wexps = calibrate_integer_weight_exps(
        lambda: build_mobilenet_v2(weights, **geometry), calib, names,
        margin=margin)
    graph = build_mobilenet_v2(weights, wexps, **geometry)
    plan_requant(graph, list(calib), margin=margin)
    return graph


def compile_mobilenet_v2(weights: Optional[Weights] = None, *,
                         calib_seeds: Sequence[int] = range(1, 3),
                         input_seed: int = 0, margin: int = 1,
                         **geometry):
    """Build, calibrate (:func:`calibrated_graph`, over the images of
    ``calib_seeds`` and ``input_seed``) and compile; returns
    ``(net, graph)``."""
    size = geometry.get("image_size", 224)
    weights = weights or mobilenet_v2_random_weights(**geometry)
    image = synthetic_image(input_seed, size)
    calib = [synthetic_image(s, size) for s in calib_seeds] + [image]
    graph = calibrated_graph(weights, calib, margin=margin, **geometry)
    return compile_graph(graph, image, calib=calib, margin=margin), graph


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

_EXACT = 2 ** 24      # float32 holds every integer below it exactly


def reference_forward(weights: Weights, images: np.ndarray,
                      params: Params, *, width: float = 1.0) -> np.ndarray:
    """Logits ``(B, 1, classes)`` int8 of int8 ``images`` ``(B, 3, S, S)``.

    Plain ``jax.numpy`` in float32 under ``default_matmul_precision(
    "highest")``: every value is an integer, every dot product is checked
    to stay below 2^24 so that float32 holds it exactly, a depthwise conv
    is ``conv_general_dilated`` with ``feature_group_count`` = channels,
    shifts are floor divisions by powers of two and the int8 truncation
    is a two's-complement wrap.  ``params`` is :func:`graph_params` of
    the planned graph, ``width`` the multiplier the weights were drawn
    at."""
    import jax
    import jax.numpy as jnp

    geo = {layer["name"]: layer for layer in linear_layers(
        width=width, image_size=images.shape[-1],
        num_classes=weights["fc"][0].shape[1])}

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
            ((p, p), (p, p)), dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=layer["groups"])
        return y + jnp.asarray(b, jnp.float32)[None, :, None, None]

    def shr(x, s):
        return jnp.floor(x / 2.0 ** s)

    def int8(x):
        return jnp.mod(x + 128.0, 256.0) - 128.0

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(images, jnp.float32)
        block_in = None
        for name, layer in geo.items():
            if name == "fc":
                break
            if name.endswith(("ex", "dw")) and block_in is None:
                block_in = x
            y = shr(conv(name, x), params[f"{name}_q"])
            if layer["relu6"]:
                y = jnp.minimum(jnp.maximum(y, 0.0), params[f"{name}_r6"])
            if layer["join"]:
                # the join adds in the accumulator: the branch is not
                # narrowed before it
                block = name[:-2]
                pa, pb = params[f"{block}_join"]
                y = shr(shr(y, pa) + shr(block_in, pb), params[f"{block}_q"])
            if name.endswith("pw") or name == "stem":
                block_in = None
            if name == "head":
                y = jnp.sum(y, axis=(2, 3), keepdims=True)
                y = shr(y, params["gap_q"])
            x = int8(y)
        x = x.reshape(x.shape[0], -1)
        w, b = weights["fc"]
        check(x, w, w.shape[0])
        x = x @ jnp.asarray(w, jnp.float32) + jnp.asarray(b, jnp.float32)
        x = int8(shr(x, params["fc_q"]))
    return np.asarray(x).astype(np.int8).reshape(len(images), 1, -1)

