"""Graph IR + pass pipeline tests (DESIGN.md §Graph).

One test per declared pass invariant, plus a seeded random-DAG fuzz whose
contract is the certification property of the whole front end: every
generated graph either compiles — and then executes bit-identically to
the graph's integer reference on both simulator backends — or raises a
typed :class:`CompileError`.  **Never wrong bytes.**

Hypothesis-free: part of the tier-1 floor.
"""

import numpy as np
import pytest

from repro.core import isa
from repro.core.errors import CompileError
from repro.graph import (GraphBuilder, compile_graph, evaluate_graph,
                         infer_shapes, linearize, plan_requant)


def _w(rng, *shape):
    return rng.integers(-6, 7, shape, dtype=np.int64).astype(np.int8)


def _b(rng, n):
    return rng.integers(-30, 31, (n,), dtype=np.int64).astype(np.int32)


def _mini_resnet(rng, shifts_pinned=False):
    """A one-join residual graph on a (1, 4, 8, 8) input."""
    q = (lambda i: [4, 5, 9, 2][i]) if shifts_pinned else (lambda i: None)
    bld = GraphBuilder("mini")
    x = bld.input("image", shape=(1, 4, 8, 8))
    v = bld.conv("s1", x, _w(rng, 8, 4, 3, 3), _b(rng, 8), padding=1)
    v = bld.relu("s1_r", v)
    v = bld.requant("s1_q", v, shift=q(0))
    skip = v
    v = bld.conv("b1a", skip, _w(rng, 8, 8, 3, 3), _b(rng, 8), padding=1)
    v = bld.relu("b1a_r", v)
    v = bld.requant("b1a_q", v, shift=q(1))
    v = bld.conv("b1b", v, _w(rng, 8, 8, 3, 3), _b(rng, 8), padding=1)
    v = bld.requant("b1b_q", v, shift=q(2))
    v = bld.add("j1", v, skip)
    v = bld.relu("j1_r", v)
    v = bld.requant("j1_q", v, shift=q(3))
    v = bld.flatten("flat", v)
    v = bld.fc("head", v, _w(rng, 8 * 8 * 8, 10), _b(rng, 10))
    v = bld.requant("head_q", v)
    bld.output(v)
    return bld.build()


def _images(rng, n, shape=(1, 4, 8, 8)):
    return [rng.integers(-40, 41, shape, dtype=np.int64).astype(np.int8)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# IR structural verification
# ---------------------------------------------------------------------------

def test_builder_rejects_unknown_refs_duplicates_and_bad_arity():
    bld = GraphBuilder("bad")
    bld.input("x", shape=(1, 1, 4, 4))
    with pytest.raises(CompileError, match="unknown value"):
        bld.relu("r", "nope")
    with pytest.raises(CompileError, match="duplicate"):
        bld.input("x", shape=(1, 1, 4, 4))
    with pytest.raises(CompileError, match="pool mode"):
        bld.pool("p", "x", mode="avg3x3")


def test_graph_verify_certifies_acyclicity():
    """A hand-mutated graph with a cycle must fail topological
    verification (the DAG certificate the passes rely on)."""
    rng = np.random.default_rng(0)
    g = _mini_resnet(rng)
    g.nodes["s1_r"].inputs = ("j1_q",)      # back edge: s1_r reads a later value
    with pytest.raises(CompileError, match="cycle"):
        g.verify()


# ---------------------------------------------------------------------------
# Pass 1: shape inference
# ---------------------------------------------------------------------------

def test_infer_shapes_resolves_every_value():
    rng = np.random.default_rng(1)
    g = _mini_resnet(rng)
    shapes = infer_shapes(g)
    assert set(shapes) == set(g.nodes)            # invariant: all resolved
    assert shapes["s1_q"] == (1, 8, 8, 8)
    assert shapes["j1"] == (1, 8, 8, 8)
    assert shapes["flat"] == (1, 512)
    assert shapes["head_q"] == (1, 10)


def test_infer_shapes_rejects_mismatched_add_and_channels():
    rng = np.random.default_rng(2)
    bld = GraphBuilder("bad")
    x = bld.input("x", shape=(1, 4, 8, 8))
    a = bld.requant("qa", bld.conv("c1", x, _w(rng, 8, 4, 3, 3), padding=1))
    d = bld.requant("qd", bld.conv("c2", x, _w(rng, 6, 4, 3, 3), padding=1))
    j = bld.add("j", a, d)
    bld.output(j)
    with pytest.raises(CompileError, match="add operands"):
        infer_shapes(bld.build())

    bld2 = GraphBuilder("bad2")
    x = bld2.input("x", shape=(1, 3, 8, 8))
    v = bld2.conv("c", x, _w(rng, 8, 4, 3, 3))       # expects 4 channels
    bld2.output(v)
    with pytest.raises(CompileError, match="channel mismatch"):
        infer_shapes(bld2.build())


# ---------------------------------------------------------------------------
# Pass 2: requant planning across joins
# ---------------------------------------------------------------------------

def test_plan_requant_equalises_scales_at_every_join():
    """Invariant: after planning, both operands of every add carry the
    same scale exponent (exp - pre_shift equal on both sides)."""
    rng = np.random.default_rng(3)
    g = _mini_resnet(rng)
    plan = plan_requant(g, _images(rng, 4))
    for name, node in g.nodes.items():
        if node.kind != "add":
            continue
        (ra, rb), (pa, pb) = node.inputs, node.pre_shifts
        assert plan.exps[ra] - pa == plan.exps[rb] - pb
        assert pa >= 0 and pb >= 0
    # every requant got a concrete shift
    assert all(g.nodes[q].shift is not None
               for q in g.nodes if g.nodes[q].kind == "requant")


def test_plan_requant_weight_exp_moves_the_join_pre_shift():
    """``weight_exp`` shifts the scale bookkeeping (not the arithmetic):
    declaring the branch convs one octave finer each must surface as a
    2-octave pre-shift on the skip operand."""
    rng = np.random.default_rng(4)
    seed_imgs = _images(rng, 4)
    g0 = _mini_resnet(np.random.default_rng(4))
    plan0 = plan_requant(g0, seed_imgs)
    base_pa, base_pb = g0.nodes["j1"].pre_shifts

    g1 = _mini_resnet(np.random.default_rng(4))
    g1.nodes["b1a"].weight_exp = plan0.shifts["b1a_q"]
    g1.nodes["b1b"].weight_exp = plan0.shifts["b1b_q"]
    g1.nodes["s1"].weight_exp = plan0.shifts["s1_q"]
    plan1 = plan_requant(g1, seed_imgs)
    pa1, pb1 = g1.nodes["j1"].pre_shifts
    # raw-integer scales: branch is far coarser, skip gets a large shift;
    # calibrated weight scales: operands land together
    assert base_pb > 0 and pb1 == 0 and base_pa == pa1 == 0
    # weight_exp is bookkeeping only: the magnitude-driven shifts
    # upstream of the join are untouched (downstream values change,
    # because the join's pre-shifts changed what flows through it)
    for q in ("s1_q", "b1a_q", "b1b_q"):
        assert plan1.shifts[q] == plan0.shifts[q]
    assert plan1.exps["j1"] == plan1.exps["b1b_q"] - pa1


def test_plan_requant_enforces_int8_feed_and_avg_pool_floor():
    rng = np.random.default_rng(5)
    bld = GraphBuilder("no_requant")
    x = bld.input("x", shape=(1, 2, 6, 6))
    v = bld.conv("c1", x, _w(rng, 4, 2, 3, 3), _b(rng, 4))
    v = bld.conv("c2", v, _w(rng, 4, 4, 3, 3))    # conv fed by raw int32 acc
    bld.output(v)
    with pytest.raises(CompileError, match="int8"):
        plan_requant(bld.build(), _images(rng, 2, (1, 2, 6, 6)))

    bld2 = GraphBuilder("tiny_avg")
    x = bld2.input("x", shape=(1, 1, 4, 4))
    v = bld2.conv("c", x, np.ones((1, 1, 1, 1), dtype=np.int8))
    v = bld2.pool("p", v, "avg2x2")
    v = bld2.requant("q", v)
    bld2.output(v)
    g2 = bld2.build()
    # all-ones weights on a tiny input: magnitudes alone would plan < 2,
    # but the device folds the avg-pool ÷4 into the same SHR
    plan = plan_requant(g2, [np.ones((1, 1, 4, 4), dtype=np.int8)], margin=0)
    assert plan.shifts["q"] >= 2


# ---------------------------------------------------------------------------
# Pass 3: linearization
# ---------------------------------------------------------------------------

def test_linearize_respects_data_dependencies_and_covers_every_node():
    rng = np.random.default_rng(6)
    g = _mini_resnet(rng)
    plan_requant(g, _images(rng, 2))
    steps = linearize(g)
    materialized = set(g.input_names)
    covered = set(g.input_names)
    for step in steps:
        assert step.input_value in materialized       # dependency order
        if step.residual_source is not None:
            assert step.residual_source in materialized
        assert not (set(step.node_names) & covered)   # exactly-once cover
        covered.update(step.node_names)
        materialized.add(step.output_value)
    assert covered == set(g.nodes)                    # full coverage
    res = [s for s in steps if s.residual_source is not None]
    assert [s.name for s in res] == ["b1b"]
    assert res[0].residual_source == "s1_q"
    assert res[0].relu and res[0].post_shift is not None


def test_linearize_folds_branch_pre_shift_into_requant():
    """(x >> q) >> pre == x >> (q + pre): the branch operand's
    scale-equalising shift must fold into the pre-add requant."""
    rng = np.random.default_rng(7)
    g = _mini_resnet(rng)
    plan_requant(g, _images(rng, 2))
    g.nodes["j1"].pre_shifts = (1, g.nodes["j1"].pre_shifts[1] + 1)
    step = [s for s in linearize(g) if s.residual_source is not None][0]
    assert step.requant_shift == g.nodes["b1b_q"].shift + 1


def test_linearize_rejects_unfusable_patterns():
    rng = np.random.default_rng(8)

    def base(bld):
        x = bld.input("x", shape=(1, 2, 8, 8))
        return bld.conv("c", x, _w(rng, 4, 2, 3, 3), padding=1)

    bld = GraphBuilder("no_requant")
    v = base(bld)
    bld.output(v)                                  # raw acc as output
    with pytest.raises(CompileError, match="consumer"):
        linearize(bld.build())

    bld = GraphBuilder("relu_twice")
    v = base(bld)
    v = bld.relu("r1", v)
    v = bld.relu("r2", v)
    v = bld.requant("q", v, shift=8)
    bld.output(v)
    with pytest.raises(CompileError, match="requant"):
        linearize(bld.build())

    bld = GraphBuilder("pool_after_join")
    v = base(bld)
    q = bld.requant("q", v, shift=8)
    bld2 = bld.conv("c2", q, _w(rng, 4, 4, 3, 3), padding=1)
    q2 = bld.requant("q2", bld2, shift=8)
    j = bld.add("j", q2, q)
    p = bld.pool("p", j, "max2x2")                 # pool of a join value
    out = bld.requant("q3", p, shift=2)
    bld.output(out)
    g = bld.build()
    g.nodes["j"].pre_shifts = (0, 0)
    with pytest.raises(CompileError):
        linearize(g)


# ---------------------------------------------------------------------------
# Random-DAG fuzz: compile or CompileError — never wrong bytes
# ---------------------------------------------------------------------------

def _random_graph(rng):
    """A random small DAG: residual blocks, pools, stride-2 downsampling
    chains, GAP heads, branches, an fc head — with a chance of
    deliberately broken structure (bad channel counts, missing requants,
    joins of mismatched shapes, stride grids that drop pixels, GAP on
    non-power-of-two maps)."""
    bld = GraphBuilder("fuzz")
    c = int(rng.integers(1, 5))
    hw = int(rng.choice([4, 6, 8]))
    x = bld.input("image", shape=(1, c, hw, hw))
    vals = [("image", c, hw)]                      # (name, channels, extent)
    uid = [0]

    def fresh(prefix):
        uid[0] += 1
        return f"{prefix}{uid[0]}"

    def conv_chain(src, sc, shw, *, relu=True, pool=None, requant=True,
                   breakage=0.0, stride=1):
        f = int(rng.integers(1, 7))
        if stride == 2:
            # k3/p1 halving or the k2/p0 projection geometry — on odd
            # extents the k2 grid drops a pixel (a wanted rejection path)
            k, pad = (3, 1) if rng.random() < 0.5 else (2, 0)
        else:
            k = int(rng.choice([1, 3]))
            pad = (k - 1) // 2
        in_c = sc if rng.random() >= breakage else sc + 1   # maybe broken
        v = bld.conv(fresh("c"), src, _w(rng, f, in_c, k, k), _b(rng, f),
                     stride=stride, padding=pad)
        shw = (shw + 2 * pad - k) // stride + 1
        if relu:
            v = bld.relu(fresh("r"), v)
        if pool and shw % 2 == 0:
            v = bld.pool(fresh("p"), v, pool)
            shw //= 2
        if requant:
            v = bld.requant(fresh("q"), v)
        return v, f, shw

    depth = int(rng.integers(1, 4))
    for _ in range(depth):
        src, sc, shw = vals[int(rng.integers(0, len(vals)))]
        kind = rng.random()
        if kind < 0.3 and shw >= 4:               # residual block
            a, fa, _ = conv_chain(src, sc, shw, relu=True)
            bvi = bld.conv(fresh("c"), a, _w(rng, sc, fa, 3, 3),
                           _b(rng, sc), padding=1)
            bq = bld.requant(fresh("q"), bvi)
            j = bld.add(fresh("j"), bq, src)
            j = bld.relu(fresh("r"), j)
            v = bld.requant(fresh("q"), j)
            vals.append((v, sc, shw))
        elif kind < 0.4:                           # deliberately unfused add
            other, oc, ohw = vals[int(rng.integers(0, len(vals)))]
            j = bld.add(fresh("j"), src, other)
            v = bld.requant(fresh("q"), j)
            vals.append((v, sc, shw))
        elif kind < 0.55 and shw >= 3:             # stride-2 downsampling
            v, f, shw2 = conv_chain(src, sc, shw, relu=bool(rng.integers(2)),
                                    stride=2)
            vals.append((v, f, shw2))
        else:                                      # plain conv chain
            pool = rng.choice([None, "max2x2", "avg2x2"])
            requant = rng.random() > 0.1           # sometimes missing
            v, f, shw2 = conv_chain(src, sc, shw, relu=bool(rng.integers(2)),
                                    pool=pool, requant=requant,
                                    breakage=0.15)
            vals.append((v, f, shw2))
    src, sc, shw = vals[int(rng.integers(0, len(vals)))]
    tail = rng.random()
    if tail < 0.25 and shw >= 1:                   # GAP head (maybe non-pow2)
        v = bld.conv(fresh("c"), src, _w(rng, sc, sc, 1, 1), _b(rng, sc))
        v = bld.relu(fresh("r"), v)
        v = bld.global_avg_pool(fresh("g"), v)
        v = bld.requant(fresh("q"), v)
        v = bld.flatten(fresh("f"), v)
        v = bld.fc(fresh("h"), v, _w(rng, sc, 5), _b(rng, 5))
        v = bld.requant(fresh("q"), v)
        bld.output(v)
    elif tail < 0.8:
        v = bld.flatten(fresh("f"), src)
        v = bld.fc(fresh("h"), v, _w(rng, sc * shw * shw, 5), _b(rng, 5))
        v = bld.requant(fresh("q"), v)
        bld.output(v)
    else:
        bld.output(src)                            # maybe an invalid output
    return bld.build(), (1, c, hw, hw)


@pytest.mark.parametrize("seed", range(40))
def test_fuzz_random_dags_compile_or_raise_never_wrong_bytes(seed):
    rng = np.random.default_rng(1000 + seed)
    try:
        graph, in_shape = _random_graph(rng)
    except CompileError:
        return                                     # builder-level rejection
    img = rng.integers(-40, 41, in_shape, dtype=np.int64).astype(np.int8)
    calib = [rng.integers(-40, 41, in_shape, dtype=np.int64).astype(np.int8)
             for _ in range(2)]
    try:
        net = compile_graph(graph, img, calib=calib + [img])
    except CompileError:
        return                                     # typed rejection is fine
    # It compiled: it must now be bit-exact against the graph reference
    # on both backends, per-image and batched.
    expected = evaluate_graph(graph, img)[graph.outputs[0]].astype(np.int8)
    out_o, _ = net.verify(backend="oracle")
    out_f, _ = net.verify(backend="fast")
    np.testing.assert_array_equal(out_o, out_f)
    np.testing.assert_array_equal(out_o.astype(np.int8), expected)
    outs, _ = net.serve([img, img])
    np.testing.assert_array_equal(outs[0].astype(np.int8), expected)
    np.testing.assert_array_equal(outs[1].astype(np.int8), expected)


def test_fuzz_produces_both_outcomes():
    """The fuzz population must contain successful compiles *and* typed
    rejections — otherwise the suite above is vacuous on one side."""
    compiled = rejected = 0
    for seed in range(40):
        rng = np.random.default_rng(1000 + seed)
        try:
            graph, in_shape = _random_graph(rng)
            img = rng.integers(-40, 41, in_shape,
                               dtype=np.int64).astype(np.int8)
            compile_graph(graph, img)
            compiled += 1
        except CompileError:
            rejected += 1
    assert compiled >= 5, f"only {compiled} fuzz graphs compiled"
    assert rejected >= 5, f"only {rejected} fuzz graphs rejected"


# ---------------------------------------------------------------------------
# The lowering's on-VTA residual contract
# ---------------------------------------------------------------------------

def test_residual_join_is_an_alu_add_on_the_vta():
    """The join must execute as a TensorAlu vector-vector ADD against an
    ACC-loaded second operand — not as host-side numpy."""
    rng = np.random.default_rng(9)
    g = _mini_resnet(rng)
    img = _images(rng, 1)[0]
    net = compile_graph(g, img, calib=_images(rng, 3) + [img])
    res = [l for l in net.layers if l.spec.residual_add]
    assert len(res) == 1
    prog = res[0].program
    adds = [i for i in prog.instructions
            if isinstance(i, isa.AluInsn)
            and i.alu_opcode == isa.AluOp.ADD and not i.use_imm]
    assert len(adds) == res[0].n_chunks           # one per chunk
    assert "res" in prog.regions                  # staged ACC operand
    res_loads = [i for i in prog.instructions
                 if isinstance(i, isa.MemInsn)
                 and i.opcode == isa.Opcode.LOAD
                 and i.memory_type == isa.MemId.ACC and i.sram_base > 0]
    assert len(res_loads) == res[0].n_chunks
