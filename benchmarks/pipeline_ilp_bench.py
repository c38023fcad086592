"""Pipeline-schedule quality: the ILP-derived schedule vs GPipe-style and
non-pipelined baselines (latency in ticks; peak in-flight activations) —
plus scheduler *compile-time* tracking (DESIGN.md §5): wall-clock rows per
config and a ``BENCH_sched_compile.json`` snapshot at the repo root so the
perf trajectory of the compilation hot path is visible across PRs."""
from __future__ import annotations

import json
import pathlib
import time

from repro.core import overlap, pipeline_ilp as pp

_BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_sched_compile.json"


def _compile_corpus_rows():
    """Compile-time rows for the paper benchmark corpus (reduced size so the
    bench run stays interactive; the shape of the trend is what matters)."""
    from repro.core.autotune import compile_program
    from repro.core.programs import fig3_conv1d, unsharp, dus, two_mm

    rows = []
    for name, mk in (("fig3", fig3_conv1d), ("unsharp16", lambda: unsharp(16)),
                     ("dus16", lambda: dus(16)), ("two_mm8", lambda: two_mm(8))):
        p = mk()
        t0 = time.perf_counter()
        compile_program(p)
        ms = (time.perf_counter() - t0) * 1e3
        rows.append((f"compile.{name}.ms", ms * 1e3, round(ms, 2)))
    return rows


def run(emit):
    print("# === pipeline-ILP schedules (paper §4.2 applied to PP) ===")
    rows = []
    compile_ms = {}
    schedules = {}
    for S, M in ((4, 8), (8, 16), (8, 32), (16, 32)):
        t0 = time.perf_counter()
        s = pp.synthesize(S, M, t_f=1, t_b=2)
        dt = time.perf_counter() - t0
        us = dt * 1e6
        compile_ms[f"S{S}M{M}"] = round(dt * 1e3, 2)
        schedules[f"S{S}M{M}"] = dict(
            ii=s.ii, latency=s.latency, fwd_start=s.fwd_start,
            bwd_start=s.bwd_start, peak=s.peak_live_activations)
        gp = pp.gpipe_latency(S, M)
        sq = pp.sequential_latency(S, M)
        rows.append((f"pp.S{S}M{M}.compile_ms", us, compile_ms[f"S{S}M{M}"]))
        rows.append((f"pp.S{S}M{M}.latency_ticks", us, s.latency))
        rows.append((f"pp.S{S}M{M}.vs_sequential", 0.0,
                     round(sq / s.latency, 3)))
        rows.append((f"pp.S{S}M{M}.vs_gpipe_latency", 0.0,
                     round(gp / s.latency, 3)))
        rows.append((f"pp.S{S}M{M}.peak_act", 0.0, s.peak_live_activations))
        rows.append((f"pp.S{S}M{M}.gpipe_peak_act", 0.0, S * M))
    t0 = time.perf_counter()
    enc = pp.synthesize(6, 8, t_f=1, backward=False, cross_from=1)
    enc_dt = time.perf_counter() - t0
    compile_ms["encdec_nonSPSC"] = round(enc_dt * 1e3, 2)
    rows.append(("pp.encdec_nonSPSC.ii", enc_dt * 1e6, enc.ii))
    for n in (4, 8, 16):
        plan = overlap.plan_ring_overlap(n)
        rows.append((f"overlap.ring{n}.ii", 0.0, plan.ii))
        rows.append((f"overlap.ring{n}.speedup_vs_serial", 0.0,
                     round(plan.overlap_speedup, 3)))

    corpus_rows = _compile_corpus_rows()
    rows.extend(corpus_rows)
    emit(rows)

    # perf-trajectory snapshot (compared across PRs; schedules included so a
    # compile-time win that silently changed a schedule is caught in review)
    snapshot = {
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "compile_ms": compile_ms,
        "corpus_compile_ms": {n.split(".")[1]: d for n, _, d in corpus_rows},
        "schedules": schedules,
    }
    _BENCH_JSON.write_text(json.dumps(snapshot, indent=1) + "\n")
    print(f"# wrote {_BENCH_JSON.name}")
