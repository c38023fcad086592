"""Resource-aware DSE driver (autotune.explore, DESIGN.md §6).

The acceptance demo: for real benchmarks, ``explore`` must find a
transformed program whose scheduled latency beats the untransformed
``compile_program`` schedule at equal-or-lower BRAM/DSP, and the winner
must pass the brute-force schedule validator + timed-execution oracle
(``validate=True`` asserts both inside explore).
"""
import pytest

from repro.core.api import explore
from repro.core.autotune import compile_program
from repro.core.programs import harris, two_mm, unsharp


@pytest.mark.parametrize("mk,n", [(two_mm, 6), (harris, 6)])
def test_explore_beats_baseline_iso_resources(mk, n):
    p = mk(n, storage="bram")
    r = explore(p, verify=True, validate=True, max_candidates=8,
                unroll_factors=(2,), tile_sizes=())
    assert r.best.latency < r.baseline.latency, (r.best.desc, r.best.latency)
    assert r.best.res["bram_bytes"] <= r.baseline.res["bram_bytes"] + 1e-9
    assert r.best.res["dsp"] <= r.baseline.res["dsp"] + 1e-9
    assert r.best.within_budget
    assert r.speedup > 1.0


def test_explore_default_budget_is_iso_resource():
    p = two_mm(4)
    r = explore(p, verify=True, max_candidates=4, unroll_factors=(),
                tile_sizes=())
    assert r.budget == {"bram_bytes": r.baseline.res["bram_bytes"],
                        "dsp": r.baseline.res["dsp"]}
    for c in r.candidates:
        assert c.within_budget == all(
            c.res[k] <= v + 1e-9 for k, v in r.budget.items())


def test_explore_budget_gates_unroll():
    """Unrolling doubles datapath DSPs: it must be flagged over-budget under
    the iso-resource budget, but become eligible when the budget allows."""
    p = unsharp(8, storage="bram")
    iso = explore(p, max_candidates=6, unroll_factors=(2,), tile_sizes=())
    unrolled = [c for c in iso.candidates if "unroll" in c.desc]
    assert unrolled and all(not c.within_budget for c in unrolled)
    assert iso.best.within_budget

    roomy = explore(p, budget={"dsp": 1e9, "bram_bytes": 1e9},
                    max_candidates=6, unroll_factors=(2,), tile_sizes=())
    unrolled = [c for c in roomy.candidates if "unroll" in c.desc]
    assert unrolled and all(c.within_budget for c in unrolled)


def test_explore_baseline_matches_compile_program():
    p = two_mm(4)
    r = explore(p, max_candidates=2, unroll_factors=(), tile_sizes=())
    assert r.baseline.latency == compile_program(p).completion_time()
    assert r.best.latency <= r.baseline.latency


def test_explore_enumerates_shifted_fusion():
    """On a mismatched-bounds chain the DSE must enumerate (and here win
    with) a shift-and-peel fused candidate under the iso-resource budget."""
    from repro.core.programs import blur_chain
    p = blur_chain(8, storage="bram")
    r = explore(p, verify=True, validate=True, max_candidates=8,
                unroll_factors=(), tile_sizes=())
    fused = [c for c in r.candidates if getattr(c.program, "_fusion_log", [])]
    assert fused, "no shifted-fusion candidate enumerated"
    best_fused = min(fused, key=lambda c: c.latency)
    assert best_fused.program._fusion_log[0]["shift"] == [2, 0]
    assert best_fused.within_budget
    assert best_fused.latency < r.baseline.latency
    assert r.best.latency <= best_fused.latency


def test_tiling_is_not_resource_neutral():
    """The tile-window footprint term (DESIGN.md §6): a nest-local
    intermediate of an explicitly tiled nest is costed at its streamed
    window, so (a) tiling changes the resource vector at all, (b) different
    tile sizes cost differently — the knob the DSE uses to pick block_rows
    for real."""
    from repro.core.dataflow import resources, tile_window_elems
    from repro.core.programs import blur_chain
    from repro.core.transforms import (FuseProducerConsumer, LoopTile,
                                       PassManager)
    from repro.core.autotune import compile_program

    p = blur_chain(8, storage="bram")
    fused = PassManager([FuseProducerConsumer()], verify=True).run(p)
    core_iv = next(it.ivname for it in fused.body if not it.peel)
    r_untiled = resources(fused, compile_program(fused), "ours")
    by_size = {}
    for s in (2, 4):
        q = PassManager([LoopTile({core_iv: s})], verify=True).run(fused)
        # window = (block + halo) rows x full width; halo = taps - 1 = 2
        assert tile_window_elems(q) == {"bx": (s + 2) * 8}
        by_size[s] = resources(q, compile_program(q), "ours")
    assert by_size[2] != r_untiled and by_size[4] != r_untiled
    assert by_size[2] != by_size[4]
    assert by_size[2]["bram_bytes"] < by_size[4]["bram_bytes"] \
        < r_untiled["bram_bytes"]
    # untiled programs are untouched by the footprint term
    assert tile_window_elems(p) == {}


def test_frontier_point_differs_by_tile_size():
    """At least one Pareto frontier point must differ from another by its
    tile size (the ISSUE acceptance for the VMEM/BRAM footprint term), and
    the tiled point must be strictly cheaper in BRAM."""
    from repro.core import hls
    from repro.core.programs import blur_chain
    from repro.core.transforms import LoopTile

    p = blur_chain(8, storage="bram")
    r = hls.compile(p, search=hls.SearchConfig(
        moves=("fuse", "tile"), unroll_factors=(), tile_sizes=(2, 4),
        max_candidates=8))

    def tile_sizes_of(c):
        out = []
        for ps in c.passes:
            if isinstance(ps, LoopTile):
                out += list(ps.seq or ps.sizes.values())
        return tuple(out)

    tiled = [c for c in r.frontier if tile_sizes_of(c)]
    untiled = [c for c in r.frontier if not tile_sizes_of(c)]
    assert tiled and untiled, [c.desc for c in r.frontier]
    assert min(c.res["bram_bytes"] for c in tiled) < \
        min(c.res["bram_bytes"] for c in untiled)
    # the stencil kernel config reads its block_rows off this exact knob,
    # via the knee point's generated kernel (emit_pallas), which rounds the
    # tile up to the chip's row tile
    from repro.core.codegen import SUBLANE_ROWS
    from repro.kernels.stencil_pipeline import (_stencil_codegen_config,
                                                stencil_config_source)
    block_rows, halo = _stencil_codegen_config()
    assert stencil_config_source() == "dse"
    assert halo == 2
    knee = r.knee("latency", "bram", among=tiled)
    assert block_rows in {-(-t // SUBLANE_ROWS) * SUBLANE_ROWS
                          for t in tile_sizes_of(knee)}


def test_metadata_only_candidates_share_pair_enumeration():
    """ArrayPartition only rewrites array metadata: a DepAnalysis over the
    partitioned clone must reuse the original's data-dependence pair
    enumeration (probed via the ``hls.data_pairs_enumerated`` counter) —
    while a transform that changes the iteration space must not."""
    from repro.core import telemetry
    from repro.core.deps import DepAnalysis
    from repro.core.transforms import ArrayPartition, LoopUnroll

    p = harris(6, storage="bram")

    def runs():
        return telemetry.counters.get("hls.data_pairs_enumerated", 0)

    before = runs()
    d1 = DepAnalysis(p)
    assert runs() == before + 1

    q = ArrayPartition().apply(p)
    d2 = DepAnalysis(q)
    assert runs() == before + 1, \
        "metadata-only clone re-ran pair enumeration"
    # the shared half must produce identical data pairs (kinds + uids)
    data = lambda d: sorted((pr.X.uid, pr.Y.uid, pr.kind) for pr in d._pairs
                            if pr.kind != "PORT")
    assert data(d1) == data(d2)

    # re-analyzing the SAME program also shares
    DepAnalysis(p)
    assert runs() == before + 1

    # an iteration-space change must NOT share
    u = LoopUnroll(2).apply(p)
    DepAnalysis(u)
    assert runs() == before + 2

    # and the shared analyses still compile to working schedules
    s = compile_program(q)
    assert s.feasible
