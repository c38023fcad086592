"""Codegen backend (DESIGN.md §10): generated Pallas kernels vs the
``sim.sequential_exec`` oracle.

The float64 lowerings run under ``jax.enable_x64`` and are
bit-comparable to the float64 numpy oracle (same DAG, same order), so the
equivalence assertions use rtol=1e-12/atol=0 — anything looser would let a
structurally wrong window/halo slip through as "close enough".
"""
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import sim  # noqa: E402
from repro.core.codegen import PallasKernel, lower_program  # noqa: E402
from repro.core.errors import UnlowerableProgram  # noqa: E402
from repro.core.ir import ProgramBuilder  # noqa: E402
from repro.core.programs import (BENCHMARKS, CHAIN_BENCHMARKS,  # noqa: E402
                                 blur_chain, fig1_conv_chain, fig3_conv1d)
from repro.core.transforms import (FuseProducerConsumer, LoopTile,  # noqa: E402
                                   Normalize, PassManager)


def _exact(kernel, p, seed=0):
    """Assert the float64 kernel (interpret mode) matches sequential_exec
    exactly on every produced output."""
    inputs = sim.make_inputs(p, seed=seed)
    ref = sim.sequential_exec(p, inputs)
    with jax.enable_x64(True):
        got = kernel(inputs, interpret=True)
    for a in kernel.outputs:
        np.testing.assert_allclose(np.asarray(got[a], np.float64), ref[a],
                                   rtol=1e-12, atol=0, err_msg=a)


# ---------------------------------------------------------------------------
# corpus coverage: every program either lowers + matches, or rejects
# structurally
# ---------------------------------------------------------------------------

_CORPUS = {**{k: v for k, v in BENCHMARKS.items()},
           **CHAIN_BENCHMARKS, "fig1_conv_chain": fig1_conv_chain}
_CORPUS_N = {"optical_flow": 6, "two_mm": 6}
# nothing in the corpus is structurally rejected anymore: two_mm's 3-deep
# canonical accumulations now lower in Mode B via an unrolled left fold
_EXPECTED_UNLOWERABLE: set = set()


@pytest.mark.parametrize("name", sorted(_CORPUS))
@pytest.mark.parametrize("buffering", ["double", "single"])
def test_corpus_equivalence(name, buffering):
    p = _CORPUS[name](_CORPUS_N.get(name, 8), storage="bram")
    if name in _EXPECTED_UNLOWERABLE:
        with pytest.raises(UnlowerableProgram):
            lower_program(p, buffering=buffering, dtype="float64")
        return
    k = lower_program(p, buffering=buffering, dtype="float64")
    assert isinstance(k, PallasKernel) and k.outputs
    _exact(k, p)


def test_fig3_conv1d_unlowerable():
    """The flipped-kernel 1-D conv reads ``w[i + j]`` — a non-separable
    (two-iv) index codegen rejects with the access named in the reason."""
    with pytest.raises(UnlowerableProgram, match="non-separable"):
        lower_program(fig3_conv1d(), dtype="float64")


def test_streamed_mode_on_chains():
    """Every mismatched-bounds chain takes the streamed (line-buffer) path,
    with a grid and a positive halo on its fused intermediate."""
    for name, mk in CHAIN_BENCHMARKS.items():
        k = lower_program(mk(8, storage="bram"))
        assert k.mode == "streamed", (name, k.soft_reasons)
        assert k.grid and k.grid[0] >= 1
        assert all(h >= 0 for h in k.halo.values())


def test_partial_tile_padding():
    """Output rows not divisible by block_rows: the last grid step computes
    into edge-padded rows and the wrapper trims them."""
    p = blur_chain(10, "bram", 3)
    k = lower_program(p, block_rows=4, dtype="float64")
    assert k.grid == (3,)
    _exact(k, p)


@pytest.mark.parametrize("name", sorted(CHAIN_BENCHMARKS))
def test_double_vs_single_bitexact(name):
    """Buffering is a schedule choice, never a numerics choice: the double-
    and single-buffered lowerings agree bit-for-bit (float32)."""
    p = CHAIN_BENCHMARKS[name](12, storage="bram")
    kd = lower_program(p, buffering="double")
    ks = lower_program(p, buffering="single")
    inputs = sim.make_inputs(p, seed=1)
    od, os_ = kd(inputs, interpret=True), ks(inputs, interpret=True)
    for a in kd.outputs:
        assert np.array_equal(np.asarray(od[a]), np.asarray(os_[a])), (name, a)


def test_bad_buffering_rejected():
    with pytest.raises(ValueError, match="buffering"):
        lower_program(blur_chain(8, "bram"), buffering="triple")


# ---------------------------------------------------------------------------
# golden: generated blur chain == hand-written stencil_pipeline, bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("buffering", ["double", "single"])
def test_blur_golden_matches_handwritten(buffering):
    """The generated blur-chain kernel reproduces the hand-written
    ``kernels/stencil_pipeline.py`` (the golden reference it generalizes)
    bit-exactly: same taps, same block_rows/halo, float32 both sides."""
    from repro.kernels.stencil_pipeline import stencil_pipeline

    n, br, taps = 16, 8, 3
    p = blur_chain(n, "bram", taps)
    k = lower_program(p, block_rows=br, buffering=buffering)

    # blur_chain's conv weights: w_t = 1 / (2^|t - mid| + 1)
    w = np.asarray([1.0 / (2 ** abs(t - (taps - 1) // 2) + 1)
                    for t in range(taps)], np.float32)
    img = np.asarray(np.random.default_rng(7).uniform(
        0.5, 2.0, p.arrays["img"].shape), np.float32)

    import jax.numpy as jnp
    hand = stencil_pipeline(jnp.asarray(img), jnp.asarray(w), jnp.asarray(w),
                            block_rows=br, halo=taps - 1, interpret=True)

    inputs = {a: np.zeros(p.arrays[a].shape) for a in p.arrays}
    inputs["img"] = img.astype(np.float64)
    gen = k(inputs, interpret=True)[k.outputs[0]]
    assert np.asarray(gen).dtype == np.float32
    assert np.array_equal(np.asarray(gen), np.asarray(hand))


# ---------------------------------------------------------------------------
# property test: randomized fused/tiled chains ≡ sequential_exec
# ---------------------------------------------------------------------------


def _random_chain(rng: random.Random):
    """A random 2-stage producer-consumer chain: conv-like stage over img
    into bx, then a row-stencil stage into out — random sizes, taps, ops and
    weights; occasionally a strided store (exercising the whole-array
    fallback's scatter-free strided writes)."""
    n = rng.randint(4, 10)
    w = rng.randint(4, 8)
    t1, t2 = rng.randint(1, 3), rng.randint(1, 3)
    ct = rng.randint(1, 2)
    strided = rng.random() < 0.2
    b = ProgramBuilder(f"rand_chain_{n}x{w}")
    H1 = n + t2 - 1                       # bx rows stage2 consumes
    b.array("img", (H1 + t1 - 1, w + ct - 1),
            partition=(0,), ports=("w", "r", "r", "r"))
    b.array("bx", (H1, w), partition=(0,), ports=("w", "r", "r", "r"))
    out_shape = (n, 2 * w) if strided else (n, w)
    b.array("out", out_shape, partition=(0,), ports=("w", "r", "r", "r"),
            is_arg=True)
    fns = ["add", "mul", "min", "max", "sub"]

    def combine(vals):
        acc = vals[0]
        for v in vals[1:]:
            acc = b.arith(rng.choice(fns), acc, v)
        return acc

    with b.loop("pi", 0, H1) as i:
        with b.loop("pj", 0, w) as j:
            vals = [b.mul(b.load("img", i + a_, j + c_),
                          b.const(round(rng.uniform(0.25, 1.5), 3)))
                    for a_ in range(t1) for c_ in range(ct)]
            b.store("bx", combine(vals), i, j)
    with b.loop("ci", 0, n) as i:
        with b.loop("cj", 0, w) as j:
            vals = [b.mul(b.load("bx", i + a_, j),
                          b.const(round(rng.uniform(0.25, 1.5), 3)))
                    for a_ in range(t2)]
            if strided:
                b.store("out", combine(vals), i, 2 * j)
            else:
                b.store("out", combine(vals), i, j)
    return b.build()


@pytest.mark.parametrize("seed", range(27))
def test_property_random_chain(seed):
    """≥25 randomized fused/tiled chains: the kernel lowered from the
    original program with the pipeline's tile size must match the
    transformed program's executable semantics exactly (float64)."""
    rng = random.Random(1000 + seed)
    p = _random_chain(rng)
    passes = [Normalize()]
    if rng.random() < 0.7:
        passes.append(FuseProducerConsumer())
    bs = rng.choice([None, 2, 3, 4])
    if bs is not None:
        # positional form: tiles the top-level nests (post-fusion names)
        passes.append(LoopTile((bs,)))
    q = PassManager(passes, verify=True).run(p)
    k = lower_program(p, block_rows=bs,
                      buffering=rng.choice(["double", "single"]),
                      dtype="float64")
    inputs = sim.make_inputs(p, seed=seed)
    ref = sim.sequential_exec(q, inputs)
    with jax.enable_x64(True):
        got = k(inputs, interpret=True)
    for a in k.outputs:
        np.testing.assert_allclose(np.asarray(got[a], np.float64), ref[a],
                                   rtol=1e-12, atol=0,
                                   err_msg=f"seed={seed} array={a} "
                                           f"mode={k.mode}")


# ---------------------------------------------------------------------------
# emit_pallas: CompileResult integration + structured rejection
# ---------------------------------------------------------------------------


def _compile_small(p):
    from repro.core import hls
    return hls.compile(
        p, objectives=("latency", "bram"),
        search=hls.SearchConfig(moves=("fuse", "tile"), unroll_factors=(),
                                tile_sizes=(2, 4), max_candidates=6))


def test_emit_pallas_from_compile_result():
    """emit_pallas defaults to the best point, picks block_rows off its tile
    pass, and carries the modeled latency + fusion shifts for the
    modeled-vs-measured loop."""
    p = blur_chain(12, "bram", 3)
    r = _compile_small(p)
    k = r.emit_pallas()
    assert k.modeled_latency == r.best.latency
    assert k.point_desc == r.best.desc
    _exact(lower_program(p, block_rows=k.block_rows, dtype="float64"), p)
    fused = [c for c in r.frontier if getattr(c.program, "_fusion_log", [])]
    if fused:
        kf = r.emit_pallas(fused[0])
        assert kf.fusion_shifts and kf.halo.get("bx", 0) >= 1
    # every design point maps to a row block the chip accepts: a multiple
    # of the sublane tile, or one block spanning the whole output
    from repro.core.codegen import SUBLANE_ROWS
    for c in r.frontier:
        kc = r.emit_pallas(c)
        assert kc.block_rows % SUBLANE_ROWS == 0 or kc.grid == (1,), c.desc


def test_emit_pallas_unlowerable_records_diagnostic():
    """An unlowerable program raises the structured CompileError subclass
    AND records a codegen-unlowerable diagnostic on the result."""
    from repro.core import CompileError

    p = fig3_conv1d()
    r = _compile_small(p)
    with pytest.raises(UnlowerableProgram, match="non-separable") as ei:
        r.emit_pallas()
    assert isinstance(ei.value, CompileError)
    assert ei.value.reasons
    assert [v.code for v in ei.value.violations] == ["non-separable"]
    ds = [d for d in r.diagnostics if d.get("kind") == "codegen-unlowerable"]
    assert (ds and ds[0]["program"] == "fig3_conv1d" and ds[0]["reasons"]
            and ds[0]["codes"] == ["non-separable"])


def test_unlowerable_reduction_reason():
    """A nest reading the array it writes (a true reduction) is rejected
    with a reason naming the recurrence, not an opaque failure."""
    b = ProgramBuilder("running_sum")
    b.array("x", (8, 4), partition=(0,), ports=("w", "r"))
    b.array("acc", (8, 4), partition=(0,), ports=("w", "r"), is_arg=True)
    with b.loop("i", 0, 7) as i:
        with b.loop("j", 0, 4) as j:
            b.store("acc", b.add(b.load("acc", i, j), b.load("x", i, j)),
                    i + 1, j)
    with pytest.raises(UnlowerableProgram):
        lower_program(b.build())


def test_kernel_source_is_the_artifact():
    """The emitted source is a self-contained module: it exec's standalone
    and exposes the same run() the kernel wraps."""
    p = blur_chain(8, "bram", 3)
    k = lower_program(p)
    assert "pl.pallas_call" in k.source and "def run(" in k.source
    ns = {}
    exec(compile(k.source, "<re-exec>", "exec"), ns)
    inputs = sim.make_inputs(p, seed=2)
    a = k.outputs[0]
    assert np.array_equal(
        np.asarray(ns["run"](inputs, interpret=True)[a]),
        np.asarray(k(inputs, interpret=True)[a]))


@pytest.mark.parametrize("step", [2, 3])
@pytest.mark.parametrize("axis", [0, 1])
def test_strided_helpers_match_numpy(axis, step):
    """The emitted ``_take`` (strided read without a strided slice) and
    ``_spread`` (repeat without a lane-splitting reshape) equal numpy's
    strided slice and repeat, including a window too short for a whole
    number of phases, which ``_take`` pads."""
    from repro.core.codegen import _STRIDED_SET_HELPER, _TAKE_HELPER

    ns = {}
    exec("import jax\nimport jax.numpy as jnp\n" + _TAKE_HELPER
         + _STRIDED_SET_HELPER, ns)
    x = np.arange(11 * 13, dtype=np.float32).reshape(11, 13)
    L = x.shape[axis]
    for start in range(L):
        for n in range(1, (L - 1 - start) // step + 2):
            want = np.take(x, range(start, start + step * (n - 1) + 1, step),
                           axis=axis)
            got = np.asarray(ns["_take"](jax.numpy.asarray(x), axis, start,
                                         n, step))
            assert np.array_equal(got, want), (start, n)
    got = np.asarray(ns["_spread"](jax.numpy.asarray(x), axis, step))
    assert np.array_equal(got, np.repeat(x, step, axis=axis))


# ---------------------------------------------------------------------------
# polyphase lowering of strided reads (Mode A)
# ---------------------------------------------------------------------------


def _strided_program(name: str, n: int):
    """Mode A programs whose reads carry strides.

    ``decimate``: one stage reading an input at stride 2 on both axes, two
    of its four phases.  ``blur_pool``: a 3 x 1 blur into an interior box
    of ``bx`` (a store offset), a 1 x 3 blur, then a 2 x 2 stride-2 max,
    so the stride passes through two unit-stride stages to the input.
    ``mixed``: an input read at strides 2 and 1 on both axes.
    ``mixed_cols``: an input read at stride 2 on every row and at strides 2
    and 1 on the columns."""
    b = ProgramBuilder(name)
    port = dict(partition=(0,), ports=("w", "r", "r", "r"))
    if name == "blur_pool":
        b.array("img", (2 * n + 2, 2 * n + 2), is_arg=True, **port)
        b.array("bx", (2 * n + 1, 2 * n + 2), **port)
        b.array("by", (2 * n, 2 * n), **port)
        b.array("out", (n, n), is_arg=True, **port)
        with b.loop("xi", 0, 2 * n) as i:
            with b.loop("xj", 0, 2 * n + 2) as j:
                b.store("bx", b.sum_tree([
                    b.mul(b.load("img", i + u, j), b.const(0.25 + 0.25 * u))
                    for u in range(3)]), i + 1, j)
        with b.loop("yi", 0, 2 * n) as i:
            with b.loop("yj", 0, 2 * n) as j:
                b.store("by", b.sum_tree([
                    b.mul(b.load("bx", i + 1, j + v), b.const(0.5 - 0.125 * v))
                    for v in range(3)]), i, j)
        with b.loop("pi", 0, n) as i:
            with b.loop("pj", 0, n) as j:
                m = None
                for u in range(2):
                    for v in range(2):
                        x = b.load("by", 2 * i + u, 2 * j + v)
                        m = x if m is None else b.arith("max", m, x)
                b.store("out", m, i, j)
        return b.build()
    b.array("img", (2 * n + 2, 2 * n + 2), is_arg=True, **port)
    b.array("out", (n, n), is_arg=True, **port)
    with b.loop("i", 0, n) as i:
        with b.loop("j", 0, n) as j:
            x = b.load("img", 2 * i, 2 * j)
            if name == "decimate":
                y = b.load("img", 2 * i + 1, 2 * j + 3)
            elif name == "mixed":
                y = b.load("img", i + 1, j)
            else:
                y = b.load("img", 2 * i + 1, j + 1)
            b.store("out", b.sub(b.mul(x, b.const(0.75)), y), i, j)
    return b.build()


@pytest.mark.parametrize("n,block_rows", [(8, 8), (12, 4), (20, 8), (26, 8)])
def test_conv_pool_lowers_to_phase_planes(n, block_rows, monkeypatch):
    """conv_pool's pool reads the blur at stride 2 on both axes: the blur
    is emitted once per phase and the input split into four planes, so the
    kernel holds no ``_take``.  Exact against the float64 oracle and
    bit-identical between double and single buffering, at sizes whose last
    row block is whole, partial, or the only one."""
    from repro.core import programs, telemetry

    p = programs.conv_pool(n, storage="bram")
    monkeypatch.setattr(telemetry, "counters", {})
    k = lower_program(p, block_rows=block_rows, dtype="float64")
    assert k.mode == "streamed"
    assert "_take" not in k.source
    assert [telemetry.counters.get(c, 0) for c in
            ("hls.lowers", "hls.phase_planes", "hls.lane_takes")] == [1, 4, 0]
    assert k.halo == {"conv": 0}
    _exact(k, p)
    inputs = sim.make_inputs(p, seed=3)
    kd, ks = (lower_program(p, block_rows=block_rows, buffering=b)
              for b in ("double", "single"))
    assert np.array_equal(np.asarray(kd(inputs, interpret=True)["pool"]),
                          np.asarray(ks(inputs, interpret=True)["pool"]))


@pytest.mark.parametrize("name,planes,takes", [
    ("decimate", ["img@0_0", "img@1_1"], 0),
    ("blur_pool", ["img@0_0", "img@0_1", "img@1_0", "img@1_1"], 0),
    ("mixed", [], 1),
    ("mixed_cols", ["img@0_0", "img@1_0"], 1),
])
@pytest.mark.parametrize("buffering", ["double", "single"])
def test_strided_reads_split_into_phases_where_strides_agree(
        name, planes, takes, buffering, monkeypatch):
    """An axis whose reads of an array all carry one stride is split into
    phase planes, the planes read being the phases the program touches; an
    axis whose strides mix keeps the array whole and decimates with
    ``_take`` (the lane-axis ones counted).  Every variant is exact."""
    from repro.core import codegen, telemetry

    p = _strided_program(name, 7)
    nests, _ = codegen._extract_nests(p)
    plan, _ = codegen._plan_streamed(p, nests, 4)
    assert sorted(plan.inputs) == (planes or ["img"])
    monkeypatch.setattr(telemetry, "counters", {})
    k = lower_program(p, block_rows=4, buffering=buffering, dtype="float64")
    assert k.mode == "streamed" and k.grid == (2,)
    assert telemetry.counters.get("hls.phase_planes", 0) == len(planes)
    assert telemetry.counters.get("hls.lane_takes", 0) == takes
    _exact(k, p)
