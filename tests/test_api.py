"""The declarative hls.compile front end (DESIGN.md §6).

Covers, per the API-redesign acceptance criteria:

  * pipeline-string parser: round-trip property (parse -> print -> parse
    identity over randomized pass sequences) + golden error messages with
    source positions;
  * malformed CompileSpec errors (objectives, constraints, targets);
  * pinned golden Pareto frontiers for blur_chain / conv_pool / harris at
    n=8 (the Fig. 9 trade-off curve is deterministic);
  * no-regression vs the old greedy explore(): the new frontier contains a
    point dominating-or-equal to the greedy winner;
  * the retired entry points (repro.core.explore / compile_program) are
    gone from the package surface.
"""
import numpy as np
import pytest

from repro.core import hls
from repro.core.autotune import _greedy_explore, dominates
from repro.core.pipeline_parse import (PipelineSyntaxError, parse_pipeline,
                                       print_pipeline)
from repro.core.programs import (CHAIN_BENCHMARKS, blur_chain, conv_pool,
                                 harris, optical_flow, two_mm)
from repro.core.transforms import (ArrayPartition, FuseProducerConsumer,
                                   LoopTile, LoopUnroll, Normalize,
                                   PASS_TAGS, PassManager, ToSPSC)


# ---------------------------------------------------------------------------
# Pipeline string syntax
# ---------------------------------------------------------------------------


def _random_pass(rng):
    k = rng.integers(0, 7)
    if k == 0:
        return Normalize() if rng.integers(0, 2) else Normalize(sink=False)
    if k == 1:
        return ToSPSC()
    if k == 2:
        ivs = None if rng.integers(0, 2) else \
            tuple(f"iv{j}" for j in range(1 + rng.integers(0, 3)))
        return LoopUnroll(int(2 ** rng.integers(1, 4)), ivs)
    if k == 3:
        if rng.integers(0, 2):
            return LoopTile(tuple(int(2 * rng.integers(1, 9))
                                  for _ in range(1 + rng.integers(0, 3))))
        return LoopTile({f"l{j}": int(2 * rng.integers(1, 9))
                         for j in range(1 + rng.integers(0, 3))})
    if k == 4:
        arrays = None if rng.integers(0, 2) else ("a", "b")
        dims = None if rng.integers(0, 2) else tuple(
            int(d) for d in range(rng.integers(1, 3)))
        return ArrayPartition(arrays, dims)
    if k == 5:
        return FuseProducerConsumer(
            None if rng.integers(0, 2) else int(rng.integers(1, 4)),
            enable_shift=bool(rng.integers(0, 2)),
            min_core_fraction=float(rng.choice([0.25, 0.5, 0.75])))
    return FuseProducerConsumer()


@pytest.mark.parametrize("seed", range(25))
def test_pipeline_roundtrip_property(seed):
    """parse(print(passes)) reproduces every pass signature, and printing
    is a fixpoint: print(parse(print(p))) == print(p)."""
    rng = np.random.default_rng(1234 + seed)
    passes = [_random_pass(rng) for _ in range(int(rng.integers(1, 6)))]
    text = print_pipeline(passes)
    parsed = parse_pipeline(text)
    assert [p.signature() for p in parsed] == \
        [p.signature() for p in passes], text
    assert print_pipeline(parsed) == text


def test_pipeline_parse_example_from_spec():
    ps = parse_pipeline("normalize,fuse{shift=true,min_core_fraction=0.5},"
                        "tile{sizes=8,8},unroll{factor=2}")
    assert [type(p) for p in ps] == [Normalize, FuseProducerConsumer,
                                     LoopTile, LoopUnroll]
    assert ps[1].enable_shift is True
    assert ps[2].seq == (8, 8)
    assert ps[3].factor == 2
    # whitespace-insensitive
    ps2 = parse_pipeline(" normalize , fuse { shift = true , "
                         "min_core_fraction = 0.5 } , tile { sizes = 8 , 8 } "
                         ", unroll { factor = 2 } ")
    assert [p.signature() for p in ps2] == [p.signature() for p in ps]


def test_pipeline_parse_empty_and_registry():
    assert parse_pipeline("") == []
    assert parse_pipeline("   ") == []
    assert set(PASS_TAGS) == {"normalize", "unroll", "tile", "partition",
                              "fuse", "spsc"}


# golden error messages: the caret must point at the offending token and the
# message must name the fix — these strings are part of the API surface
_GOLDEN_ERRORS = [
    ("frobnicate",
     "unknown pass 'frobnicate' (known: fuse, normalize, partition, spsc, "
     "tile, unroll)\n  at position 0:"),
    ("fuse{shift=banana}",
     "fuse shift: expected bool, got 'banana'\n  at position 0:"),
    ("normalize{sink=banana}",
     "normalize sink: expected bool, got 'banana'\n  at position 0:"),
    ("normalize{sank=true}",
     "normalize: unknown parameter(s) ['sank'] (valid: sink)\n"
     "  at position 0:"),
    ("unroll{ivs=i,j}",
     "unroll requires factor=<int>\n  at position 0:"),
    ("tile{8,8}",
     "value '8' has no parameter name (write key=value)\n  at position 5:"),
    ("tile{i=4,sizes=8}",
     "tile: cannot mix sizes= with named loops ['i']\n  at position 0:"),
    ("unroll{factor=2",
     "expected ',' or '}' in the parameter block, got end of input\n"
     "  at position 15:"),
    ("fuse,,tile{i=4}",
     "expected a pass name, got ','\n  at position 5:"),
    ("fuse{shift=true,shift=false}",
     "duplicate parameter 'shift'\n  at position 16:"),
    ("fuse,",
     "trailing ',' with no pass after it\n  at position 4:"),
]


@pytest.mark.parametrize("text,prefix",
                         _GOLDEN_ERRORS, ids=[t for t, _ in _GOLDEN_ERRORS])
def test_pipeline_parse_golden_errors(text, prefix):
    with pytest.raises(PipelineSyntaxError) as ei:
        parse_pipeline(text)
    msg = str(ei.value)
    assert msg.startswith(prefix), f"\ngot:  {msg!r}\nwant prefix: {prefix!r}"
    # the caret line (4-space indented source echo) points at the position
    assert msg.splitlines()[-1] == " " * (4 + ei.value.pos) + "^"
    assert 0 <= ei.value.pos <= len(text)


# ---------------------------------------------------------------------------
# Spec validation (malformed-spec goldens)
# ---------------------------------------------------------------------------


def test_malformed_spec_errors():
    with pytest.raises(ValueError, match=r"unknown objective 'brams'"):
        hls.minimize("brams")
    with pytest.raises(ValueError, match=r"weight must be > 0"):
        hls.minimize("latency", weight=0)
    with pytest.raises(ValueError, match=r"malformed constraint 'bram >= 3'"):
        hls.Constraint.parse("bram >= 3")
    with pytest.raises(ValueError, match=r"unknown constraint resource"):
        hls.Constraint.parse("latency <= 10")
    with pytest.raises(ValueError, match=r"exactly one of limit= .* scale="):
        hls.Constraint("bram")
    with pytest.raises(ValueError, match=r"unknown target mode 'fpga'"):
        hls.Target(mode="fpga")
    with pytest.raises(ValueError, match=r"unknown capacity resource"):
        hls.Target(capacities={"sram": 1})
    with pytest.raises(ValueError, match=r"unknown combine mode 'sum'"):
        hls.CompileSpec(combine="sum")
    with pytest.raises(ValueError, match=r"at least one objective"):
        hls.CompileSpec(objectives=())
    with pytest.raises(TypeError, match=r"spec must be a CompileSpec"):
        hls.compile(two_mm(4), {"objective": "latency"})


def test_constraint_parse_forms():
    c = hls.Constraint.parse("dsp <= 48")
    assert (c.resource, c.limit, c.scale) == ("dsp", 48.0, None)
    c = hls.Constraint.parse("bram <= 1.5x baseline")
    assert (c.resource, c.limit, c.scale) == ("bram_bytes", None, 1.5)
    assert hls.constraint("ff <= 2.0x baseline").resource == "ff_bits"


# ---------------------------------------------------------------------------
# Fixed-pipeline compilation
# ---------------------------------------------------------------------------


def test_fixed_pipeline_matches_manual_composition():
    p = blur_chain(8, storage="bram")
    r = hls.compile(p, pipeline="fuse")
    q = PassManager([FuseProducerConsumer()], verify=True).run(p)
    from repro.core.autotune import compile_program as raw_compile
    assert r.best.latency == raw_compile(q).completion_time()
    assert r.best.desc == "fuse"
    assert r.frontier == [r.best]
    # the printed pipeline of the result re-parses to the same design
    r2 = hls.compile(p, pipeline=r.pipeline_of())
    assert r2.best.latency == r.best.latency


def test_fixed_pipeline_with_trailing_noop_keeps_applied_passes():
    """A fixed pipeline whose LAST pass happens not to fire must still
    deliver the earlier passes' design — only a wholly no-op pipeline
    degrades to the baseline (regression: the DSE's incremental no-op
    convention leaked into the fixed-pipeline path and silently returned
    the baseline)."""
    p = blur_chain(8, storage="bram")
    r = hls.compile(p, pipeline="fuse,normalize")  # normalize is a no-op
    fused = hls.compile(p, pipeline="fuse")
    assert r.best.latency == fused.best.latency
    assert r.best.program._fusion_log
    # wholly no-op pipeline -> baseline
    r0 = hls.compile(p, pipeline="normalize")
    assert r0.best is r0.baseline


def test_empty_pipeline_is_compile_program():
    p = two_mm(4)
    from repro.core.autotune import compile_program as raw_compile
    r = hls.compile(p, pipeline=())
    assert r.best is r.baseline
    assert r.best.latency == raw_compile(p).completion_time()
    assert r.schedule is r.best.schedule


def test_fixed_pipeline_capacity_rejection():
    p = blur_chain(8, storage="bram")
    r = hls.compile(p, pipeline="fuse", constraints=("dsp <= 1",))
    assert r.frontier == []
    assert not r.best.within_budget
    assert r.rejected and "dsp" in r.rejected[0][1]
    assert "over budget" in r.explain()


# ---------------------------------------------------------------------------
# Pareto frontiers
# ---------------------------------------------------------------------------


def _frontier_tuples(r):
    return [(c.latency, c.res["bram_bytes"], c.res["dsp"], c.res["ff_bits"])
            for c in r.frontier]


# Golden frontiers (latency, bram_bytes, dsp, ff_bits), objective-sorted.
# Regenerate with the same SearchConfig if the resource model or scheduler
# changes intentionally; any other drift is a regression.
_GOLDEN_FRONTIERS = {
    "blur_chain": dict(
        n=8, max_candidates=12, unroll_factors=(2,), tile_sizes=(2, 4),
        frontier=[
            (67, 0, 52, 9280),      # fuse | partition | unroll(x2)
            (103, 0, 26, 7328),     # fuse | partition | tile(core:2)
            (103, 1568, 26, 1056),  # fuse | tile(core:2)
            (103, 1952, 26, 992),   # fuse
            (106, 1952, 26, 512),   # baseline
        ]),
    "conv_pool": dict(
        n=8, max_candidates=12, unroll_factors=(2,), tile_sizes=(2, 4),
        frontier=[
            (52, 0, 86, 12288),     # fuse | partition | unroll(x2)
            (73, 0, 43, 10720),     # fuse | partition
            (84, 1440, 43, 6400),   # fuse
            (92, 0, 43, 6432),      # partition
            (92, 1440, 43, 704),    # baseline
        ]),
    "harris": dict(
        n=8, max_candidates=6, unroll_factors=(2,), tile_sizes=(),
        frontier=[
            (157, 0, 157, 23488),   # partition
            (225, 4800, 157, 3392), # baseline
            (268, 4800, 157, 2112), # fuse(noshift)
        ]),
}
_GOLDEN_MAKERS = {"blur_chain": blur_chain, "conv_pool": conv_pool,
                  "harris": harris}


@pytest.mark.parametrize("name", sorted(_GOLDEN_FRONTIERS))
def test_golden_pareto_frontier(name):
    g = _GOLDEN_FRONTIERS[name]
    p = _GOLDEN_MAKERS[name](g["n"], storage="bram")
    r = hls.compile(p, search=hls.SearchConfig(
        max_candidates=g["max_candidates"],
        unroll_factors=g["unroll_factors"], tile_sizes=g["tile_sizes"]))
    assert _frontier_tuples(r) == g["frontier"]
    # structural invariants: mutual non-dominance, feasibility, best on it
    for c in r.frontier:
        assert c.within_budget
        assert not any(dominates(d.objectives(), c.objectives())
                       for d in r.frontier if d is not c)
    assert r.best in r.frontier
    # a >= 2-point NON-degenerate frontier: two mutually non-dominated
    # points with distinct latency AND distinct BRAM
    assert any(c1.latency != c2.latency and
               c1.res["bram_bytes"] != c2.res["bram_bytes"]
               for c1 in r.frontier for c2 in r.frontier)


def test_objective_selection_modes():
    p = blur_chain(8, storage="bram")
    r = hls.compile(p, search=hls.SearchConfig(max_candidates=12,
                                               unroll_factors=(2,),
                                               tile_sizes=(2, 4)))
    lat = hls.compile(p, spec=None, objectives=hls.minimize("latency"),
                      search=r.spec.search)
    assert lat.best.latency == min(c.latency for c in lat.frontier)
    # lexicographic (bram, latency): min-BRAM first, latency breaks ties
    bram_first = hls.compile(
        p, objectives=(hls.minimize("bram"), hls.minimize("latency")),
        search=r.spec.search)
    min_bram = min(c.res["bram_bytes"] for c in bram_first.frontier)
    assert bram_first.best.res["bram_bytes"] == min_bram
    assert bram_first.best.latency == min(
        c.latency for c in bram_first.frontier
        if c.res["bram_bytes"] == min_bram)
    # weighted: an overwhelming BRAM weight must agree with bram-lex on
    # the chosen point's BRAM
    w = hls.compile(p, objectives=(hls.minimize("bram", weight=100.0),
                                   hls.minimize("latency")),
                    combine="weighted", search=r.spec.search)
    assert w.best.res["bram_bytes"] == min_bram


def test_constraints_cap_the_frontier():
    p = blur_chain(8, storage="bram")
    r = hls.compile(p, constraints=("dsp <= 1.0x baseline",
                                    "bram <= 1.0x baseline"),
                    search=hls.SearchConfig(max_candidates=12,
                                            unroll_factors=(2,),
                                            tile_sizes=(2, 4)))
    base = r.baseline.res
    assert r.caps == {"dsp": base["dsp"], "bram_bytes": base["bram_bytes"]}
    for c in r.frontier:
        assert c.res["dsp"] <= base["dsp"] + 1e-9
        assert c.res["bram_bytes"] <= base["bram_bytes"] + 1e-9
    # the unrolled point (2x DSP) must be among the rejected with a reason
    assert any("unroll" in desc and "dsp" in reason
               for desc, reason in r.rejected)
    assert "over budget" in r.explain()


def test_knee_point():
    p = blur_chain(8, storage="bram")
    r = hls.compile(p, search=hls.SearchConfig(max_candidates=12,
                                               unroll_factors=(),
                                               tile_sizes=(2, 4)))
    k = r.knee("latency", "bram")
    assert k in r.frontier
    # knee of a 2-point degenerate set is the single closest point
    with pytest.raises(ValueError, match="empty frontier"):
        r.knee(among=[])


_NOREG_SIZES = {"blur_chain": 8, "correlated_chain": 8, "gradient_harris": 6,
                "two_mm": 6}


@pytest.mark.parametrize("name", sorted(_NOREG_SIZES))
def test_frontier_dominates_greedy_winner(name):
    """No regression vs the old greedy single-frontier search: the Pareto
    frontier must contain a point dominating-or-equal to the greedy
    explore() winner."""
    from repro.core.programs import correlated_chain, gradient_harris
    makers = {"blur_chain": blur_chain, "correlated_chain": correlated_chain,
              "gradient_harris": gradient_harris, "two_mm": two_mm}
    p = makers[name](_NOREG_SIZES[name], storage="bram")
    g = _greedy_explore(p, max_candidates=12)
    r = hls.compile(p, search=hls.SearchConfig(max_candidates=12))
    gv = g.best.objectives()
    assert any(dominates(c.objectives(), gv) or c.objectives() == gv
               for c in r.frontier), (gv, _frontier_tuples(r))


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(list(CHAIN_BENCHMARKS) +
                                        ["harris", "optical_flow", "two_mm"]))
def test_frontier_dominates_greedy_winner_full(name):
    """The acceptance sweep: every CHAIN_BENCHMARKS + harris / optical_flow
    / two_mm program, frontier point dominating-or-equal the greedy
    winner."""
    makers = {**CHAIN_BENCHMARKS, "harris": harris,
              "optical_flow": optical_flow, "two_mm": two_mm}
    sizes = {"blur_chain": 8, "conv_pool": 8, "gradient_harris": 6,
             "correlated_chain": 8, "harris": 6, "optical_flow": 6,
             "two_mm": 6}
    p = makers[name](sizes[name], storage="bram")
    g = _greedy_explore(p, max_candidates=12)
    r = hls.compile(p, search=hls.SearchConfig(max_candidates=12))
    gv = g.best.objectives()
    assert any(dominates(c.objectives(), gv) or c.objectives() == gv
               for c in r.frontier), (gv, _frontier_tuples(r))


# ---------------------------------------------------------------------------
# Retired entry points
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["compile_program", "explore"])
def test_retired_entry_points_are_gone(name):
    """``repro.core`` no longer serves the retired DSE entry points; the
    schedule-only compile stays in ``repro.core.autotune``."""
    import repro.core
    from repro.core.autotune import compile_program
    with pytest.raises(ImportError):
        exec(f"from repro.core import {name}", {})
    assert not hasattr(repro.core, name)
    assert not hasattr(hls, name)
    assert compile_program(two_mm(4)).feasible


# ---------------------------------------------------------------------------
# Graceful empty-budget behavior
# ---------------------------------------------------------------------------


def test_explore_rejecting_budget_returns_baseline():
    """A DSP constraint no candidate can meet must return the baseline
    gracefully (no ZeroDivisionError, no arbitrary over-budget 'winner')
    and record every rejection reason."""
    p = two_mm(4)
    r = hls.compile(p, constraints=("dsp <= 0",), search=hls.SearchConfig(
        max_candidates=4, unroll_factors=(), tile_sizes=()))
    assert r.best is r.baseline
    assert not r.best.within_budget
    assert not r.frontier
    assert r.speedup == 1.0          # guarded division
    assert r.rejected and all("dsp" in reason for _, reason in r.rejected)
    assert "over budget" in r.explain()


def test_dse_speedup_guard_degenerate_latency():
    from repro.core.autotune import DSECandidate, DSEResult
    c = DSECandidate(desc="baseline", passes=(), program=None, schedule=None,
                     latency=0, res={"bram_bytes": 0.0, "dsp": 0.0,
                                     "ff_bits": 0.0, "lut": 0.0},
                     within_budget=True)
    r = DSEResult(baseline=c, best=c, candidates=[c])
    assert r.speedup == 1.0
    assert r.table() == [("baseline", 0, 0.0, 0.0, True)]
