"""Benchmark harness — one section per paper table/figure, plus the
compiler's own suites.  Prints ``name,us_per_call,derived`` CSV.

``python benchmarks/run.py`` runs everything; ``python benchmarks/run.py
SUITE`` runs one suite.  Unknown suite names are a hard argparse error (the
old ``sys.argv[1]`` filter silently ran nothing).
"""
from __future__ import annotations

import argparse

#: every runnable suite — argparse rejects anything else
SUITES = ("paper", "reg", "bram", "dse", "pareto", "dse-perf", "faults",
          "fusion", "codegen", "trace", "analysis", "pipeline", "kernels")


def _emit(rows):
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="Run the benchmark suites (all by default).")
    ap.add_argument("suite", nargs="?", choices=SUITES, metavar="suite",
                    help=f"one of: {', '.join(SUITES)}")
    only = ap.parse_args(argv).suite

    from benchmarks import paper

    for storage in ("reg", "bram"):
        if only and only not in ("paper", storage):
            continue
        res = paper.compute(storage=storage)
        print("# === paper Fig.7 — multi-dim pipelining vs loop-only "
              f"[{storage}] (paper band: 1.7-3.7x, avg 2.42x) ===")
        rows = paper.fig7(res)
        _emit([(f"fig7.{storage}.{n}", us, d) for n, us, d in rows])
        avg = sum(d for _, _, d in rows) / len(rows)
        print(f"fig7.{storage}.average,0.0,{avg:.3f}")

        print("# === paper Fig.8 — vs Vitis-dataflow model on SPSC variants "
              f"[{storage}] (paper: ours avg 1.30x over dataflow) ===")
        _emit([(f"fig8.{storage}.{n}", us, d) for n, us, d in paper.fig8(res)])

        print("# === paper Fig.9 — resource model relative to Vitis-seq "
              f"[{storage}] ===")
        _emit([(f"fig9.{storage}.{n}", us, d) for n, us, d in paper.fig9(res)])

        print("# === paper Fig.10 — unmodified non-SPSC workloads "
              f"[{storage}] (paper band: 2-2.9x) ===")
        _emit([(f"fig10.{storage}.{n}", us, d) for n, us, d in paper.fig10(res)])

    if only in (None, "dse"):
        print("# === pass-pipeline DSE — transformed program vs untransformed "
              "compile_program under the iso-resource budget (DESIGN.md §6) ===")
        # always re-run: this section IS the verification sweep, a cached
        # replay would hide transform/DSE regressions (the JSON still caches
        # for read-only consumers like dse_table)
        res = paper.compute_dse(storage="bram", force=True)
        _emit([(f"dse.bram.{n}", us, d) for n, us, d in paper.dse_table(res)])

    if only in (None, "pareto"):
        print("# === Pareto-frontier DSE — hls.compile frontier sizes + "
              "latency x BRAM hypervolume vs the old greedy explore() winner "
              "(DESIGN.md §6) ===")
        # always re-run: this section IS the no-regression check (it raises
        # when a frontier stops dominating the greedy winner)
        res = paper.compute_pareto(storage="bram", force=True)
        _emit([(f"pareto.bram.{n}", us, d)
               for n, us, d in paper.pareto_table(res)])

    if only in (None, "dse-perf"):
        print("# === serving-scale DSE — persistent-cache warm/cold + "
              "parallel frontier expansion (DESIGN.md §8) ===")
        # always re-run against a fresh tmpdir store: this section IS the
        # determinism + speedup gate (it raises when the warm-cache speedup
        # drops under the floor, a frontier stops being byte-identical
        # across cold/warm/parallel, or stops dominating the greedy oracle)
        res = paper.compute_dse_perf(storage="bram", force=True)
        _emit([(f"dse_perf.bram.{n}", us, d)
               for n, us, d in paper.dse_perf_table(res)])

    if only in (None, "faults"):
        print("# === fault tolerance — chaos runs vs the clean frontier: "
              "recovery overhead + degraded hypervolume (DESIGN.md §9) ===")
        # always re-run: this section IS the failure-handling gate (it
        # raises when a recovered-fault run moves the frontier, when a
        # divergent degraded frontier goes unlabeled, or when the recovery
        # overhead blows past the ceiling)
        res = paper.compute_faults(storage="bram", force=True)
        _emit([(f"faults.bram.{n}", us, d)
               for n, us, d in paper.faults_table(res)])

    if only in (None, "fusion"):
        print("# === shift-and-peel fusion — mismatched-bounds stencil chains, "
              "fused vs unfused schedule (DESIGN.md §6) ===")
        # always re-run: this section verifies every fused candidate
        # differentially and the winner against the brute-force oracles
        res = paper.compute_fusion(storage="bram", force=True)
        _emit([(f"fusion.bram.{n}", us, d) for n, us, d in paper.fusion_table(res)])

    if only in (None, "codegen"):
        print("# === codegen — generated Pallas kernels: measured wall-clock "
              "(interpret, double vs single buffering) next to modeled "
              "latency (DESIGN.md §10) ===")
        # always re-run: this section IS the modeled-vs-measured drift gate
        # (it raises when double-buffering stops beating single on >= 2
        # chains, outputs stop being bit-identical across bufferings, a
        # kernel diverges from sequential_exec, or a chain's normalized
        # measured/modeled ratio leaves the pinned band)
        res = paper.compute_codegen(storage="bram", force=True)
        _emit([(f"codegen.bram.{n}", us, d)
               for n, us, d in paper.codegen_table(res)])

    if only in (None, "trace"):
        print("# === tracing frontend — traced JAX kernels (wkv6 scan, conv "
              "block, attention): frontier size + modeled speedup "
              "(DESIGN.md §11) ===")
        # always re-run: this section IS the frontend acceptance gate (it
        # raises when a traced program diverges from its source kernel or
        # when a traced frontier collapses to a single point)
        res = paper.compute_trace(storage="bram", force=True)
        _emit([(f"trace.bram.{n}", us, d) for n, us, d in paper.trace_table(res)])

    if only in (None, "analysis"):
        print("# === static verifier — linter + independent schedule "
              "validation wall-clock, and the mutation-kill gate "
              "(DESIGN.md §12) ===")
        # always re-run: this section IS the verifier acceptance gate (it
        # raises on any corpus lint error, any rejected genuine schedule,
        # or any accepted corrupted schedule)
        res = paper.compute_analysis(storage="bram", force=True)
        _emit([(f"analysis.bram.{n}", us, d)
               for n, us, d in paper.analysis_table(res)])

    # a phase that fails raises, so the run exits non-zero
    if only in (None, "pipeline"):
        from benchmarks import pipeline_ilp_bench
        pipeline_ilp_bench.run(_emit)

    if only in (None, "kernels"):
        from benchmarks import kernel_bench
        kernel_bench.run(_emit)


if __name__ == "__main__":
    main()
