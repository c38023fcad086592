"""Paper-table benchmarks (Figs. 7-10 of the paper), computed from the ILP
scheduler + the Vitis-dataflow model.  Results are cached as JSON because the
optical-flow scheduling ILPs take ~1 min on this 1-core container."""
from __future__ import annotations

import json
import os
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
CACHE = os.path.join(RESULTS_DIR, "paper_results.json")
# DSE snapshot lives at the repo root next to BENCH_sched_compile.json so
# the transform/DSE win trajectory is visible across PRs.
DSE_JSON = os.path.join(os.path.dirname(__file__), os.pardir, "BENCH_dse.json")

# Fused-vs-unfused latency snapshot for the mismatched-bounds stencil
# chains (shift-and-peel fusion), next to BENCH_dse.json for the same reason.
FUSION_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                           "BENCH_fusion.json")

# The iso-resource budget of the DSE and fusion sweeps: the untransformed
# design's own BRAM and DSP are the ceilings.
ISO_RESOURCE = ("bram <= 1.0x baseline", "dsp <= 1.0x baseline")

# Reduced benchmark sizes for the DSE sweep (the DSE compiles ~a dozen
# candidates per program and validates the winner with the brute-force
# oracles, so full-size optical flow would take minutes on this container).
_DSE_SIZES = {"unsharp": 16, "harris": 8, "dus": 16, "optical_flow": 8,
              "two_mm": 8}

_FUSION_SIZES = {"blur_chain": 16, "conv_pool": 16, "gradient_harris": 12,
                 "correlated_chain": 16}

# Pareto-frontier DSE snapshot (hls.compile): frontier sizes + hypervolume
# vs the old greedy explore() winner, next to the other BENCH_*.json files.
PARETO_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                           "BENCH_pareto.json")

_PARETO_SIZES = {"blur_chain": 8, "conv_pool": 8, "gradient_harris": 6,
                 "correlated_chain": 8, "harris": 6, "optical_flow": 6,
                 "two_mm": 6}

# Codegen modeled-vs-measured snapshot (DESIGN.md §10), next to the other
# BENCH_*.json files.
CODEGEN_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                            "BENCH_codegen.json")

# The DSE runs at a small n (the sweep compiles ~a dozen candidates on this
# 1-core container); the winning pipeline is re-applied and lowered at the
# bench size, where the tile stream is long enough for the double-buffer
# refill/compute overlap to show up in interpret-mode wall-clock.
_CODEGEN_DSE_SIZES = {"blur_chain": 8, "conv_pool": 8,
                      "gradient_harris": 6, "correlated_chain": 8}
_CODEGEN_BENCH_SIZES = {"blur_chain": 128, "conv_pool": 128,
                        "gradient_harris": 96, "correlated_chain": 128}

# Drift gate: measured us (double-buffered) / modeled cycles per chain,
# normalized by the run's geometric mean — the absolute us-per-cycle scale
# depends on the host, but a chain whose NORMALIZED ratio leaves this band
# means the cost model and the generated kernel disagree in a
# chain-specific way.  Pinned from the first recorded runs on this
# container (normalized ratios 0.67-1.40 across the four chains) with
# headroom for interpret-mode timing noise.
CODEGEN_DRIFT_BAND = (0.4, 2.5)


def compute(storage: str = "reg", force: bool = False) -> dict:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    cache = {}
    if os.path.exists(CACHE) and not force:
        cache = json.load(open(CACHE))
    if storage in cache:
        return cache[storage]

    from repro.core.autotune import compile_program
    from repro.core.dataflow import (resources, to_spsc,
                                     vitis_dataflow_latency)
    from repro.core.programs import BENCHMARKS

    out = {}
    for name, mk in BENCHMARKS.items():
        t0 = time.time()
        p = mk(storage=storage)
        s = compile_program(p)
        sp = to_spsc(p)
        ss = compile_program(sp)
        vitis_df, info = vitis_dataflow_latency(sp, ss)
        rec = {
            "ours_orig": s.completion_time(),
            "loop_only_orig": s.sequential_nests_latency(),
            "ours_spsc": ss.completion_time(),
            "loop_only_spsc": ss.sequential_nests_latency(),
            "vitis_dataflow_spsc": vitis_df,
            "dataflow_applicable": info.applicable,
            "channels": [(c.array, c.kind) for c in info.channels]
            if info.applicable else info.reason,
            "iis": {l.ivname: s.iis[l.uid] for l in p.loops()},
            "resources_ours": resources(sp, ss, "ours"),
            "resources_vitis_seq": resources(sp, ss, "vitis_seq"),
            "resources_vitis_df": resources(sp, ss, "vitis_dataflow"),
            "delay_reg_bits": ss.delay_register_bits(),
            "schedule_seconds": round(time.time() - t0, 2),
        }
        out[name] = rec
    cache[storage] = out
    json.dump(cache, open(CACHE, "w"), indent=1)
    return out


def compute_dse(storage: str = "bram", force: bool = False) -> dict:
    """Resource-aware DSE sweep (DESIGN.md §6): for every benchmark, search
    transform pipelines under the iso-resource budget (baseline BRAM/DSP as
    the ceiling) and record the winner.  Results go to ``BENCH_dse.json``."""
    cache = {}
    if os.path.exists(DSE_JSON):
        cache = json.load(open(DSE_JSON))
    if storage in cache and not force:
        return cache[storage]

    from repro.core import hls
    from repro.core.programs import BENCHMARKS

    out = {}
    for name, mk in BENCHMARKS.items():
        n = _DSE_SIZES.get(name, 8)
        p = mk(n, storage=storage)
        t0 = time.time()
        r = hls.compile(p, constraints=ISO_RESOURCE,
                        search=hls.SearchConfig(max_candidates=16,
                                                validate=True))
        out[name] = {
            "n": n,
            "baseline_latency": r.baseline.latency,
            "best_latency": r.best.latency,
            "best_pipeline": r.best.desc,
            "speedup": round(r.speedup, 3),
            "budget": r.caps,
            "baseline_resources": r.baseline.res,
            "best_resources": r.best.res,
            "verified": True,   # verify + validate raised on any
                                # differential / validate_schedule failure
            "candidates": [
                {"pipeline": c.desc, "latency": c.latency,
                 "bram_bytes": c.res["bram_bytes"], "dsp": c.res["dsp"],
                 "within_budget": c.within_budget}
                for c in sorted(r.candidates, key=_candidate_order)],
            "dse_seconds": round(time.time() - t0, 2),
        }
    cache[storage] = out
    json.dump(cache, open(DSE_JSON, "w"), indent=1)
    return out


def _candidate_order(c) -> tuple:
    """Within-budget candidates first, then by latency, BRAM and DSP."""
    return (not c.within_budget, c.latency, c.res["bram_bytes"], c.res["dsp"])


def compute_fusion(storage: str = "bram", force: bool = False) -> dict:
    """Shift-and-peel fusion sweep over the mismatched-bounds stencil chains
    (``programs.CHAIN_BENCHMARKS``): for every chain, compare the unfused
    ``compile_program`` schedule against the best DSE candidate whose
    pipeline actually fused the chain (nonzero shift / peels recorded in the
    program's ``_fusion_log``).  Results go to ``BENCH_fusion.json``."""
    cache = {}
    if os.path.exists(FUSION_JSON):
        cache = json.load(open(FUSION_JSON))
    if storage in cache and not force:
        return cache[storage]

    from repro.core import hls
    from repro.core.programs import CHAIN_BENCHMARKS

    out = {}
    for name, mk in CHAIN_BENCHMARKS.items():
        n = _FUSION_SIZES.get(name, 8)
        p = mk(n, storage=storage)
        t0 = time.time()
        r = hls.compile(p, constraints=ISO_RESOURCE,
                        search=hls.SearchConfig(
                            max_candidates=10, unroll_factors=(),
                            tile_sizes=(4,), validate=True))
        fused = [c for c in r.candidates
                 if getattr(c.program, "_fusion_log", [])]
        if not fused:
            raise RuntimeError(
                f"fusion sweep: no fused candidate for chain '{name}' "
                f"(n={n}, storage={storage}) — candidates: "
                f"{[c.desc for c in r.candidates]}")
        in_budget = [c for c in fused if c.within_budget]
        best_fused = min(in_budget or fused, key=lambda c: c.latency)
        log = best_fused.program._fusion_log
        out[name] = {
            "n": n,
            "unfused_latency": r.baseline.latency,
            "fused_latency": best_fused.latency,
            "fused_pipeline": best_fused.desc,
            "loop_only_latency":
                r.baseline.schedule.sequential_nests_latency(),
            "shift": log[0]["shift"],
            "peels": sum(e["peels"] for e in log),
            "speedup": round(r.baseline.latency / best_fused.latency, 3),
            "within_budget": best_fused.within_budget,
            "budget": r.caps,
            "fused_resources": best_fused.res,
            "baseline_resources": r.baseline.res,
            "verified": True,   # verify + validate raised on any
                                # differential/validator failure
            "fusion_seconds": round(time.time() - t0, 2),
        }
    cache[storage] = out
    json.dump(cache, open(FUSION_JSON, "w"), indent=1)
    return cache[storage]


def compute_codegen(storage: str = "bram", force: bool = False) -> dict:
    """Close the modeled-vs-measured loop (DESIGN.md §10): for every
    mismatched-bounds chain, run the DSE at a small size, re-apply the
    latency x BRAM knee point's pipeline at the bench size, lower it with
    ``codegen.lower_program`` in both bufferings, and record measured
    interpret-mode wall-clock next to the modeled latency.  Gates (raise):

    * the double-buffered lowering beats the single-buffered one on >= 2
      chains (the ping-pong overlap must be real, not just modeled),
    * double and single outputs are bit-identical (buffering is a schedule
      choice, never a numerics choice),
    * the generated kernel matches ``sim.sequential_exec``,
    * every chain's normalized measured/modeled ratio stays inside
      ``CODEGEN_DRIFT_BAND``.

    Results go to ``BENCH_codegen.json``."""
    cache = {}
    if os.path.exists(CODEGEN_JSON):
        cache = json.load(open(CODEGEN_JSON))
    if storage in cache and not force:
        return cache[storage]

    import functools
    import math

    import jax
    import numpy as np

    from repro.core import hls, sim
    from repro.core.autotune import measure_candidate
    from repro.core.codegen import _point_block_rows, lower_program
    from repro.core.dataflow import tile_window_elems
    from repro.core.programs import CHAIN_BENCHMARKS

    def time_us(fn, arrays, iters=20):
        jax.block_until_ready(fn(arrays))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(arrays))
        return (time.perf_counter() - t0) / iters * 1e6

    out = {}
    for name, mk in CHAIN_BENCHMARKS.items():
        nd = _CODEGEN_DSE_SIZES.get(name, 8)
        nb = _CODEGEN_BENCH_SIZES.get(name, 96)
        t0 = time.time()
        r = hls.compile(
            mk(nd, storage=storage),
            objectives=(hls.minimize("latency"), hls.minimize("bram")),
            search=hls.SearchConfig(moves=("fuse", "tile"),
                                    unroll_factors=(), tile_sizes=(2, 4),
                                    max_candidates=8))
        knee = r.knee("latency", "bram")
        p_big = mk(nb, storage=storage)
        # modeled latency: the knee's pipeline re-applied at the bench size
        big = measure_candidate(p_big, knee.desc, list(knee.passes),
                                verify=False, incremental=False)
        if big is None:  # the knee was the baseline / pipeline no-op'd
            big = measure_candidate(p_big, "baseline", [], verify=False)
        # the kernel lowers the ORIGINAL program: the tile pass maps to
        # block_rows (the Pallas grid), the fusion shift to the window halo
        bw = _point_block_rows(knee)
        kd = lower_program(p_big, block_rows=bw, buffering="double")
        ks = lower_program(p_big, block_rows=bw, buffering="single")
        inputs = sim.make_inputs(p_big, seed=0)
        fd = jax.jit(functools.partial(kd.fn, interpret=True))
        fs = jax.jit(functools.partial(ks.fn, interpret=True))
        od, os_ = fd(inputs), fs(inputs)
        bitexact = all(np.array_equal(np.asarray(od[a]), np.asarray(os_[a]))
                       for a in kd.outputs)
        if not bitexact:
            raise RuntimeError(
                "codegen bench: double- and single-buffered lowerings of "
                f"'{name}' (n={nb}) disagree bitwise")
        ref = sim.sequential_exec(p_big, inputs)
        for a in kd.outputs:
            np.testing.assert_allclose(
                np.asarray(od[a], np.float64), ref[a], rtol=2e-3, atol=1e-4,
                err_msg=f"codegen bench: generated kernel for '{name}' "
                        f"(n={nb}) diverges from sequential_exec")
        us_d, us_s = time_us(fd, inputs), time_us(fs, inputs)
        out[name] = {
            "dse_n": nd, "bench_n": nb,
            "pipeline": knee.desc,
            "mode": kd.mode, "buffered_grid": list(kd.grid or ()),
            "block_rows": kd.block_rows, "halo": kd.halo,
            "modeled_latency": big.latency,
            "measured_us_double": round(us_d, 2),
            "measured_us_single": round(us_s, 2),
            "double_speedup": round(us_s / us_d, 3),
            "bitexact_double_vs_single": bitexact,
            "vmem_window_elems_double":
                tile_window_elems(big.program, buffers=2),
            "codegen_seconds": round(time.time() - t0, 2),
        }
    wins = [n for n, rec in out.items() if rec["double_speedup"] > 1.0]
    if len(wins) < 2:
        raise RuntimeError(
            "codegen bench: double-buffering beats single-buffering only "
            f"on {wins} — need >= 2 chains")
    ratios = {n: rec["measured_us_double"] / max(rec["modeled_latency"], 1)
              for n, rec in out.items()}
    gm = math.exp(sum(math.log(v) for v in ratios.values()) / len(ratios))
    for n, rec in out.items():
        rec["drift_normalized"] = round(ratios[n] / gm, 3)
        lo, hi = CODEGEN_DRIFT_BAND
        if not (lo <= rec["drift_normalized"] <= hi):
            raise RuntimeError(
                f"codegen bench: modeled-vs-measured drift on '{n}': "
                f"normalized ratio {rec['drift_normalized']} outside "
                f"[{lo}, {hi}]")
    cache[storage] = out
    json.dump(cache, open(CODEGEN_JSON, "w"), indent=1)
    return out


def codegen_table(res: dict) -> list[tuple]:
    """Measured wall-clock (interpret) next to modeled latency, per chain."""
    rows = []
    for name, r in res.items():
        rows.append((f"{name}.measured_double", r["measured_us_double"],
                     f"modeled={r['modeled_latency']}"))
        rows.append((f"{name}.measured_single", r["measured_us_single"],
                     f"double_speedup={r['double_speedup']}"))
        rows.append((f"{name}.drift_normalized", 0.0,
                     r["drift_normalized"]))
        rows.append((f"{name}.config", 0.0,
                     f"block_rows={r['block_rows']};grid="
                     + "x".join(map(str, r["buffered_grid"]))))
    return rows


# Tracing-frontend snapshot (DESIGN.md §11), next to the other
# BENCH_*.json files: frontier size + modeled speedup for the traced
# kernels, proving real JAX functions flow through trace -> DSE end-to-end.
TRACE_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                          "BENCH_trace.json")


def compute_trace(storage: str = "bram", force: bool = False) -> dict:
    """Trace the bundled JAX kernels (wkv6 scan, separable conv block,
    softmax attention) into Program IR, differentially validate each traced
    program against its source function, and run the Pareto DSE on the
    result.  Gates (raise):

    * every traced program matches its source kernel under
      ``sequential_exec`` at rtol=1e-12 (the differential contract),
    * every traced program's frontier has >= 2 points (a single-point
      frontier means the DSE found no latency/BRAM tradeoff on the traced
      IR — the generalized nest contract regressed).

    Results go to ``BENCH_trace.json``.  ``storage`` is recorded for cache
    symmetry with the other suites; traced arrays always use the frontend's
    dual-read BRAM preset."""
    cache = {}
    if os.path.exists(TRACE_JSON):
        cache = json.load(open(TRACE_JSON))
    if storage in cache and not force:
        return cache[storage]

    from repro.core import frontend, hls
    from repro.core.autotune import measure_candidate
    from repro.core.ir import nest_shape

    traced = {
        "wkv6": frontend.wkv6_program,
        "conv_block": frontend.conv_block_program,
        "attention": frontend.attention_program,
    }
    out = {}
    for name, mk in traced.items():
        t0 = time.time()
        tp = mk()
        err = tp.validate(seed=0, rtol=1e-12)  # raises past rtol
        r = hls.compile(tp.program,
                        objectives=(hls.minimize("latency"),
                                    hls.minimize("bram")))
        if len(r.frontier) < 2:
            raise RuntimeError(
                f"trace bench: '{name}' ({tp.program.name}) produced a "
                "single-point frontier — the traced IR stopped being "
                "DSE-searchable")
        base = measure_candidate(tp.program, "baseline", [], verify=False)
        best = min(c.latency for c in r.frontier)
        out[name] = {
            "program": tp.program.name,
            "nest_kinds": list(nest_shape(tp.program).kinds),
            "validate_max_rel_err": float(err),
            "frontier_size": len(r.frontier),
            "baseline_latency": int(base.latency),
            "best_latency": int(best),
            "modeled_speedup": round(base.latency / max(best, 1), 3),
            "trace_seconds": round(time.time() - t0, 2),
        }
    cache[storage] = out
    json.dump(cache, open(TRACE_JSON, "w"), indent=1)
    return out


def trace_table(res: dict) -> list[tuple]:
    """Frontier size + modeled speedup per traced kernel."""
    rows = []
    for name, r in res.items():
        rows.append((f"{name}.frontier_size", 0.0, r["frontier_size"]))
        rows.append((f"{name}.modeled_speedup", 0.0,
                     f"{r['modeled_speedup']} "
                     f"(base={r['baseline_latency']},"
                     f"best={r['best_latency']})"))
        rows.append((f"{name}.validate", 0.0,
                     f"max_rel_err={r['validate_max_rel_err']:.2e};"
                     f"kinds={'+'.join(r['nest_kinds'])}"))
    return rows


def _hypervolume2d(points: list[tuple], ref: tuple) -> float:
    """Dominated 2D hypervolume (minimization) of ``points`` w.r.t. the
    reference corner ``ref``: the area between the non-dominated staircase
    and ``ref``.  Points beyond the reference contribute nothing."""
    pts = sorted({(min(x, ref[0]), min(y, ref[1])) for x, y in points})
    hv = 0.0
    last_y = ref[1]
    for x, y in pts:
        if y < last_y:
            hv += (ref[0] - x) * (last_y - y)
            last_y = y
    return hv


def compute_pareto(storage: str = "bram", force: bool = False) -> dict:
    """Pareto-frontier DSE sweep (hls.compile, DESIGN.md §6): for every
    mismatched-bounds chain plus harris/optical_flow/two_mm, record the
    frontier (pipelines + objective vectors), its latency x BRAM
    hypervolume normalized to the baseline design, and the comparison
    against the old greedy single-frontier explore() winner — the frontier
    must contain a point dominating-or-equal to it (no regression).
    Results go to ``BENCH_pareto.json``."""
    cache = {}
    if os.path.exists(PARETO_JSON):
        cache = json.load(open(PARETO_JSON))
    if storage in cache and not force:
        return cache[storage]

    from repro.core import hls
    from repro.core.autotune import _greedy_explore, dominates
    from repro.core.programs import (CHAIN_BENCHMARKS, harris, optical_flow,
                                     two_mm)

    progs = {**CHAIN_BENCHMARKS, "harris": harris,
             "optical_flow": optical_flow, "two_mm": two_mm}
    out = {}
    for name, mk in progs.items():
        n = _PARETO_SIZES.get(name, 8)
        p = mk(n, storage=storage)
        t0 = time.time()
        greedy = _greedy_explore(p, max_candidates=16)
        r = hls.compile(p, search=hls.SearchConfig(max_candidates=16))
        base = r.baseline

        def norm(c):
            return (c.latency / max(base.latency, 1),
                    c.res["bram_bytes"] / max(base.res["bram_bytes"], 1.0))

        ref = (1.05, 1.05)  # just beyond the baseline corner
        gv = greedy.best.objectives()
        out[name] = {
            "n": n,
            "baseline": {"latency": base.latency, **base.res},
            "frontier_size": len(r.frontier),
            "frontier": [
                {"pipeline": r.pipeline_of(c), "latency": c.latency, **c.res}
                for c in r.frontier],
            "hypervolume": round(
                _hypervolume2d([norm(c) for c in r.frontier], ref), 5),
            "greedy_hypervolume": round(
                _hypervolume2d([norm(greedy.best)], ref), 5),
            "greedy_winner": {"pipeline": greedy.best.desc,
                              "latency": greedy.best.latency,
                              **greedy.best.res},
            "dominates_greedy": bool(any(
                dominates(c.objectives(), gv) or c.objectives() == gv
                for c in r.frontier)),
            "best_pipeline": r.pipeline_of(),
            "knee_pipeline": r.pipeline_of(r.knee("latency", "bram")),
            "pareto_seconds": round(time.time() - t0, 2),
        }
        if not out[name]["dominates_greedy"]:
            raise RuntimeError(
                f"pareto sweep: frontier of '{name}' (n={n}) contains no "
                f"point dominating-or-equal the greedy winner {gv}")
    cache[storage] = out
    json.dump(cache, open(PARETO_JSON, "w"), indent=1)
    return out


def pareto_table(res: dict) -> list[tuple]:
    """Frontier size + hypervolume vs the greedy winner, per program."""
    rows = []
    for name, r in res.items():
        rows.append((f"{name}.frontier_size", r["pareto_seconds"] * 1e6,
                     r["frontier_size"]))
        rows.append((f"{name}.hypervolume", 0.0, r["hypervolume"]))
        rows.append((f"{name}.greedy_hypervolume", 0.0,
                     r["greedy_hypervolume"]))
        rows.append((f"{name}.dominates_greedy", 0.0,
                     int(r["dominates_greedy"])))
        rows.append((f"{name}.knee", 0.0,
                     r["knee_pipeline"].replace(",", ";") or "baseline"))
    return rows


def fusion_table(res: dict) -> list[tuple]:
    """Fused-vs-unfused latency of the mismatched-bounds stencil chains."""
    rows = []
    for name, r in res.items():
        rows.append((f"{name}.speedup", r["fusion_seconds"] * 1e6,
                     r["speedup"]))
        rows.append((f"{name}.fused_latency", 0.0, r["fused_latency"]))
        rows.append((f"{name}.unfused_latency", 0.0, r["unfused_latency"]))
        rows.append((f"{name}.shift", 0.0,
                     "x".join(map(str, r["shift"]))))
    return rows


def dse_table(res: dict) -> list[tuple]:
    """The DSE column: latency speedup of the explored winner over the
    untransformed compile_program schedule, at equal-or-lower BRAM/DSP."""
    rows = []
    for name, r in res.items():
        rows.append((f"{name}.speedup", r["dse_seconds"] * 1e6, r["speedup"]))
        rows.append((f"{name}.winner", 0.0,
                     r["best_pipeline"].replace(",", ";")))
        rows.append((f"{name}.bram_ratio", 0.0, round(
            r["best_resources"]["bram_bytes"] /
            max(r["baseline_resources"]["bram_bytes"], 1.0), 3)))
    return rows


def fig7(res: dict) -> list[tuple]:
    """Speedup of multi-dimensional pipelining over loop-only pipelining
    (paper: 1.7x-3.7x, avg 2.42x)."""
    rows = []
    for name, r in res.items():
        rows.append((name, r["schedule_seconds"] * 1e6,
                     round(r["loop_only_orig"] / r["ours_orig"], 3)))
    return rows


def fig8(res: dict) -> list[tuple]:
    """SPSC workloads: ours and Vitis-dataflow vs Vitis-no-dataflow
    (paper: ours avg 1.30x over Vitis dataflow)."""
    rows = []
    for name, r in res.items():
        if not r["dataflow_applicable"]:
            continue  # the paper also dropped 2mm here
        base = r["loop_only_spsc"]
        rows.append((f"{name}.vitis_df", 0.0, round(base / r["vitis_dataflow_spsc"], 3)))
        rows.append((f"{name}.ours", 0.0, round(base / r["ours_spsc"], 3)))
        rows.append((f"{name}.ours_over_df", 0.0,
                     round(r["vitis_dataflow_spsc"] / r["ours_spsc"], 3)))
    return rows


def fig9(res: dict) -> list[tuple]:
    """Resource usage relative to Vitis-no-dataflow (model)."""
    rows = []
    for name, r in res.items():
        if not r["dataflow_applicable"]:
            continue
        for metric in ("bram_bytes", "ff_bits", "lut", "dsp"):
            base = max(r["resources_vitis_seq"][metric], 1.0)
            rows.append((f"{name}.{metric}.vitis_df", 0.0,
                         round(r["resources_vitis_df"][metric] / base, 3)))
            rows.append((f"{name}.{metric}.ours", 0.0,
                         round(r["resources_ours"][metric] / base, 3)))
    return rows


def fig10(res: dict) -> list[tuple]:
    """Unmodified (non-SPSC) workloads: ours vs Vitis-no-dataflow
    (paper: 2x-2.9x)."""
    rows = []
    for name, r in res.items():
        rows.append((name, 0.0,
                     round(r["loop_only_orig"] / r["ours_orig"], 3)))
    return rows


# ---------------------------------------------------------------------------
# Serving-scale DSE perf: persistent cache + parallel expansion (DESIGN.md §8)
# ---------------------------------------------------------------------------

# Cold/warm/parallel wall-clock of the hls.compile Pareto search over a
# fresh persistent store, next to the other BENCH_*.json snapshots.
DSE_PERF_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                             "BENCH_dse_perf.json")

# The CI gate (weekly job): warm-over-cold speedup floor per program, and
# the frontier must keep dominating the greedy explore() winner.
WARM_SPEEDUP_FLOOR = 5.0
PARALLEL_SPEEDUP_FLOOR = 2.0   # enforced only on machines with >= 4 cores


def _frontier_sig(r) -> list:
    """Everything observable about a frontier point, schedule included —
    cold, warm and parallel runs must agree on this exactly."""
    return [(c.desc, c.latency,
             {k: c.res[k] for k in ("bram_bytes", "dsp", "ff_bits")},
             sorted(c.schedule.iis.values()),
             sorted(c.schedule.theta.values()))
            for c in r.frontier]


def compute_dse_perf(storage: str = "bram", force: bool = False,
                     jobs: int = 4) -> dict:
    """Serving-scale DSE benchmark (DESIGN.md §8): for every
    mismatched-bounds chain plus harris/optical_flow/two_mm, time the
    hls.compile Pareto search (a) cold against a fresh persistent store,
    (b) warm against the store the cold run just filled, and (c) with the
    expansion waves fanned across ``jobs`` worker processes (store off, so
    it measures parallel compile, not cache hits).  Frontiers must be
    byte-identical across all three runs and must keep dominating the
    greedy explore() oracle; the warm run must clear
    ``WARM_SPEEDUP_FLOOR``.  Results go to ``BENCH_dse_perf.json``."""
    cache = {}
    if os.path.exists(DSE_PERF_JSON):
        cache = json.load(open(DSE_PERF_JSON))
    if storage in cache and not force:
        return cache[storage]

    import shutil
    import tempfile

    from repro.core import hls
    from repro.core.autotune import _greedy_explore, dominates
    from repro.core.programs import (CHAIN_BENCHMARKS, harris, optical_flow,
                                     two_mm)

    progs = {**CHAIN_BENCHMARKS, "harris": harris,
             "optical_flow": optical_flow, "two_mm": two_mm}
    # hermetic: the bench always starts from an empty store in a tmpdir —
    # a warm ~/.cache/repro-hls must not fake the cold numbers
    saved = {k: os.environ.get(k)
             for k in ("REPRO_HLS_CACHE", "REPRO_HLS_CACHE_DIR")}
    tmp = tempfile.mkdtemp(prefix="repro-hls-bench-")
    os.environ["REPRO_HLS_CACHE"] = "1"
    os.environ["REPRO_HLS_CACHE_DIR"] = tmp
    out = {}
    try:
        for name, mk in progs.items():
            n = _PARETO_SIZES.get(name, 8)

            def run(use_cache: bool, use_jobs: int = 1):
                t0 = time.time()
                r = hls.compile(mk(n, storage=storage),
                                search=hls.SearchConfig(
                                    max_candidates=16, jobs=use_jobs,
                                    cache=use_cache))
                return r, time.time() - t0

            cold_r, cold_s = run(True)
            warm_r, warm_s = run(True)
            par_r, par_s = run(False, use_jobs=jobs)
            greedy = _greedy_explore(mk(n, storage=storage),
                                     max_candidates=16)
            gv = greedy.best.objectives()

            sig = _frontier_sig(cold_r)
            rec = {
                "n": n,
                "cold_seconds": round(cold_s, 3),
                "warm_seconds": round(warm_s, 3),
                "parallel_seconds": round(par_s, 3),
                "warm_speedup": round(cold_s / max(warm_s, 1e-9), 1),
                "parallel_speedup": round(cold_s / max(par_s, 1e-9), 2),
                "parallel_jobs": jobs,
                "cpu_count": os.cpu_count(),
                "compiles_to_frontier": cold_r.compiles,
                "frontier_size": len(cold_r.frontier),
                "warm_cache_hits": sum(c.cached for c in warm_r.candidates),
                "frontier_identical_warm": _frontier_sig(warm_r) == sig,
                "frontier_identical_parallel": _frontier_sig(par_r) == sig,
                "dominates_greedy": bool(any(
                    dominates(c.objectives(), gv) or c.objectives() == gv
                    for c in cold_r.frontier)),
            }
            out[name] = rec
            if not (rec["frontier_identical_warm"]
                    and rec["frontier_identical_parallel"]):
                raise RuntimeError(
                    f"dse-perf: '{name}' frontier differs across "
                    "cold/warm/parallel runs — the cache or the parallel "
                    "merge broke determinism")
            if rec["warm_speedup"] < WARM_SPEEDUP_FLOOR:
                raise RuntimeError(
                    f"dse-perf: '{name}' warm-cache speedup "
                    f"{rec['warm_speedup']}x is under the "
                    f"{WARM_SPEEDUP_FLOOR}x floor "
                    f"(cold {cold_s:.2f}s, warm {warm_s:.2f}s)")
            if not rec["dominates_greedy"]:
                raise RuntimeError(
                    f"dse-perf: frontier of '{name}' no longer contains a "
                    f"point dominating-or-equal the greedy winner {gv}")
            if ((os.cpu_count() or 1) >= 4
                    and rec["parallel_speedup"] < PARALLEL_SPEEDUP_FLOOR):
                raise RuntimeError(
                    f"dse-perf: '{name}' jobs={jobs} speedup "
                    f"{rec['parallel_speedup']}x is under the "
                    f"{PARALLEL_SPEEDUP_FLOOR}x floor on a "
                    f"{os.cpu_count()}-core machine")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    cache[storage] = out
    json.dump(cache, open(DSE_PERF_JSON, "w"), indent=1)
    return out


def dse_perf_table(res: dict) -> list[tuple]:
    """Warm/parallel speedups + search effort, per program."""
    rows = []
    for name, r in res.items():
        rows.append((f"{name}.warm_speedup", r["cold_seconds"] * 1e6,
                     r["warm_speedup"]))
        rows.append((f"{name}.parallel_speedup", r["parallel_seconds"] * 1e6,
                     r["parallel_speedup"]))
        rows.append((f"{name}.compiles_to_frontier", 0.0,
                     r["compiles_to_frontier"]))
        rows.append((f"{name}.frontier_identical", 0.0,
                     int(r["frontier_identical_warm"]
                         and r["frontier_identical_parallel"])))
        rows.append((f"{name}.dominates_greedy", 0.0,
                     int(r["dominates_greedy"])))
    return rows


# ---------------------------------------------------------------------------
# Fault-tolerance benchmark (DESIGN.md §9): chaos runs vs the clean frontier
# ---------------------------------------------------------------------------

FAULTS_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                           "BENCH_faults.json")

# CI gate: recovered-fault runs must reproduce the clean frontier exactly,
# and a divergent frontier without provenance="degraded" fails the bench.
RECOVERY_OVERHEAD_CEIL = 25.0  # recovered-run wall-clock vs clean, max ratio


def _frontier_hv(r, ref_objs) -> float:
    """Latency x BRAM x DSP x FF hypervolume of ``r.frontier`` against a
    shared reference box (1.1x the axis-max over ``ref_objs``), normalized
    per axis so no unit dominates."""
    from repro.core.autotune import _hv

    if not r.frontier or not ref_objs:
        return 0.0
    lo = [min(col) for col in zip(*ref_objs)]
    hi = [max(col) for col in zip(*ref_objs)]
    span = [h - l if h > l else 1.0 for l, h in zip(lo, hi)]
    pts = [tuple((x - l) / s for x, l, s in
                 zip(c.objectives(), lo, span)) for c in r.frontier]
    return _hv(pts, tuple(1.1 for _ in lo))


def compute_faults(storage: str = "bram", force: bool = False) -> dict:
    """Chaos benchmark: for every mismatched-bounds chain run hls.compile
    (a) clean, (b) under a recovered-fault schedule (every worker's first
    attempt crashes, retries succeed), and (c) under a degrading schedule
    (every dependence/legality ILP times out at the root).  Gates: the
    recovered run must be frontier-identical to clean with "exact"
    provenance and bounded wall-clock overhead; the degraded run must
    either match the clean frontier or carry provenance="degraded" —
    an unlabeled divergent frontier fails the bench.  Results (hypervolume
    ratio degraded/clean, recovery overhead) go to ``BENCH_faults.json``."""
    cache = {}
    if os.path.exists(FAULTS_JSON):
        cache = json.load(open(FAULTS_JSON))
    if storage in cache and not force:
        return cache[storage]

    from repro.core import faults, hls
    from repro.core.programs import CHAIN_BENCHMARKS

    # hermetic: chaos runs must not read or poison a developer's store
    saved = os.environ.get("REPRO_HLS_CACHE")
    os.environ["REPRO_HLS_CACHE"] = "0"
    out = {}
    try:
        for name, mk in CHAIN_BENCHMARKS.items():
            n = _PARETO_SIZES.get(name, 8)

            def run(plan=None, jobs=1):
                t0 = time.time()
                search = hls.SearchConfig(max_candidates=16, jobs=jobs,
                                          cache=False,
                                          worker_deadline_s=60.0)
                if plan is None:
                    r = hls.compile(mk(n, storage=storage), search=search)
                else:
                    with faults.inject(**plan):
                        r = hls.compile(mk(n, storage=storage),
                                        search=search)
                return r, time.time() - t0

            clean_r, clean_s = run()
            sig = _frontier_sig(clean_r)
            ref_objs = [c.objectives() for c in clean_r.frontier]
            hv_clean = _frontier_hv(clean_r, ref_objs)

            rec_r, rec_s = run(dict(seed=0, worker_crash=1.0,
                                    crash_attempts=(0,)), jobs=2)
            deg_r, deg_s = run(dict(seed=0, solver_timeout=1.0))

            rec = {
                "n": n,
                "clean_seconds": round(clean_s, 3),
                "recovered_seconds": round(rec_s, 3),
                "degraded_seconds": round(deg_s, 3),
                "recovery_overhead": round(rec_s / max(clean_s, 1e-9), 2),
                "frontier_size": len(clean_r.frontier),
                "recovered_identical": _frontier_sig(rec_r) == sig,
                "recovered_provenance": rec_r.provenance,
                "recovered_retries": sum(
                    d.get("kind") == "worker-retry"
                    for d in rec_r.diagnostics),
                "degraded_identical": _frontier_sig(deg_r) == sig,
                "degraded_provenance": deg_r.provenance,
                "degraded_frontier_size": len(deg_r.frontier),
                "hv_clean": round(hv_clean, 4),
                "hv_degraded": round(_frontier_hv(deg_r, ref_objs), 4),
                "hv_ratio": round(
                    _frontier_hv(deg_r, ref_objs) / max(hv_clean, 1e-9), 3),
            }
            out[name] = rec
            if clean_r.provenance != "exact":
                raise RuntimeError(
                    f"faults: clean run of '{name}' claims degraded "
                    "provenance — the fault harness leaked into a "
                    "fault-free compile")
            if not rec["recovered_identical"] \
                    or rec["recovered_provenance"] != "exact":
                raise RuntimeError(
                    f"faults: '{name}' recovered-fault frontier diverged "
                    f"from clean (identical={rec['recovered_identical']}, "
                    f"provenance={rec['recovered_provenance']}) — retried "
                    "worker faults must be invisible in the result")
            if not rec["degraded_identical"] \
                    and rec["degraded_provenance"] != "degraded":
                raise RuntimeError(
                    f"faults: '{name}' degraded run diverged from the "
                    "clean frontier WITHOUT provenance='degraded' — "
                    "unlabeled divergence is unsound")
            if rec["recovery_overhead"] > RECOVERY_OVERHEAD_CEIL:
                raise RuntimeError(
                    f"faults: '{name}' recovery overhead "
                    f"{rec['recovery_overhead']}x exceeds the "
                    f"{RECOVERY_OVERHEAD_CEIL}x ceiling "
                    f"(clean {clean_s:.2f}s, recovered {rec_s:.2f}s)")
    finally:
        if saved is None:
            os.environ.pop("REPRO_HLS_CACHE", None)
        else:
            os.environ["REPRO_HLS_CACHE"] = saved
    cache[storage] = out
    json.dump(cache, open(FAULTS_JSON, "w"), indent=1)
    return out


def faults_table(res: dict) -> list[tuple]:
    """Recovery overhead + degraded-vs-clean hypervolume, per program."""
    rows = []
    for name, r in res.items():
        rows.append((f"{name}.recovery_overhead", r["recovered_seconds"] * 1e6,
                     r["recovery_overhead"]))
        rows.append((f"{name}.recovered_identical", 0.0,
                     int(r["recovered_identical"])))
        rows.append((f"{name}.degraded_labeled", 0.0,
                     int(r["degraded_identical"]
                         or r["degraded_provenance"] == "degraded")))
        rows.append((f"{name}.hv_ratio", r["degraded_seconds"] * 1e6,
                     r["hv_ratio"]))
    return rows


ANALYSIS_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                             "BENCH_analysis.json")


def compute_analysis(storage: str = "bram", force: bool = False) -> dict:
    """Static-verifier benchmark (DESIGN.md §12): per mismatched-bounds
    chain, (a) lint the program and wall-clock the linter, (b) compile and
    run the independent schedule validator on the winner, (c) fire 25
    seeded schedule corruptions at the validator.  Gates (raise):

    * the corpus lints with zero error-severity findings,
    * every genuine winner schedule is accepted,
    * every corrupted schedule is rejected (the mutation-kill property).

    Results go to ``BENCH_analysis.json``."""
    cache = {}
    if os.path.exists(ANALYSIS_JSON):
        cache = json.load(open(ANALYSIS_JSON))
    if storage in cache and not force:
        return cache[storage]

    import numpy as np

    from repro.core import hls
    from repro.core.analysis import corrupt_schedule, lint, validate_static
    from repro.core.programs import CHAIN_BENCHMARKS

    out = {}
    for name, mk in CHAIN_BENCHMARKS.items():
        n = _PARETO_SIZES.get(name, 8)
        p = mk(n=n)
        t0 = time.time()
        diags = lint(p)
        lint_s = time.time() - t0
        errors = [d for d in diags if d.severity == "error"]
        if errors:
            raise AssertionError(
                f"{name}: lint errors {[str(d) for d in errors]}")
        r = hls.compile(p, pipeline=())
        s = r.best.schedule
        t0 = time.time()
        v = validate_static(s.program, s)
        val_s = time.time() - t0
        if not v.ok:
            raise AssertionError(
                f"{name}: golden schedule rejected: "
                f"{[str(d) for d in v.diagnostics]}")
        rng = np.random.default_rng(20260807)
        killed = tries = 0
        t0 = time.time()
        while killed < 25 and tries < 250:
            tries += 1
            made = corrupt_schedule(s, rng)
            if made is None:
                continue
            mut, info = made
            if validate_static(mut.program, mut, fail_fast=True).ok:
                raise AssertionError(f"{name}: validator accepted "
                                     f"corrupted schedule {info}")
            killed += 1
        mut_s = time.time() - t0
        out[name] = {
            "lint_findings": len(diags), "lint_seconds": lint_s,
            "pairs": v.pairs, "cases": v.cases, "ilp_calls": v.ilp_calls,
            "validate_seconds": val_s,
            "mutants_killed": killed, "mutation_seconds": mut_s,
        }

    cache[storage] = out
    json.dump(cache, open(ANALYSIS_JSON, "w"), indent=1)
    return out


def analysis_table(res: dict) -> list[tuple]:
    """Linter/validator wall-clock + mutation-kill rate, per chain."""
    rows = []
    for name, r in res.items():
        rows.append((f"{name}.lint", r["lint_seconds"] * 1e6,
                     f"findings={r['lint_findings']}"))
        rows.append((f"{name}.validate", r["validate_seconds"] * 1e6,
                     f"pairs={r['pairs']};cases={r['cases']};"
                     f"ilp={r['ilp_calls']}"))
        rows.append((f"{name}.mutation_kill", r["mutation_seconds"] * 1e6,
                     f"{r['mutants_killed']}/{r['mutants_killed']}"))
    return rows
