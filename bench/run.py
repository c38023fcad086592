"""Run one cell of the chip benchmark once and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name from ``BENCHMARK.json`` (see ``bench/spec.py``).  The run
makes its inputs on the device from ``--seed``, compiles and warms up the
cell's kernel (``setup_s``), measures for ``--seconds``, and then checks a
sample of the answers the window produced against the configuration's
float64 reference.  With ``--trace 0`` it reports the cell's end-to-end
metrics; with ``--trace 1`` it traces a stretch after the window and
reports the per-layer metrics.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and, last, ``checks``: each compared number with its limit, which
also end standard error.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.

JAX's persistent compilation cache is kept in ``bench/.jax_cache`` inside
the checkout.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from dataclasses import dataclass  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # import the benchmark as the package ``bench`` and the program from
    # ``src``; without the program beside it the run fails here
    sys.path[0] = _ROOT
    sys.path.insert(1, os.path.join(_ROOT, "src"))
    # libtpu would log under /tmp otherwise
    os.environ["TPU_LOG_DIR"] = "disabled"

CACHE_DIR = os.path.join(_ROOT, "bench", ".jax_cache")
TRACE_DIR = os.path.join(_ROOT, "bench", ".trace")


@dataclass
class Readings:
    """What a per-layer metric's reader reads."""
    calls: int          # kernel calls (frames) in the traced stretch
    trace: object       # devtrace.Summary, or None
    spans: dict         # host span name -> durations in the window, s
    ops: int            # the algorithm's operations per call
    nbytes: int         # the algorithm's HBM bytes per call
    peak: object        # peaks.Peak
    program: dict | None = None  # the program's spans (program_readings)


def configure_jax_cache() -> None:
    """The persistent compilation cache at a fixed path inside the
    checkout, holding every program, however quick its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@contextmanager
def _no_cache_writes():
    """Nothing compiled inside is written to the persistent cache.  A later
    run with the same seed draws the same constants, and its compiles must
    not be served from this run's: each compile of the recompile mix is
    cold, as an edit makes it."""
    import jax
    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    jax.config.update(key, 1e9)
    try:
        yield
    finally:
        jax.config.update(key, old)


@contextmanager
def _program_record(on: bool):
    """The program's own span record (``telemetry.recording()``) around
    the body where ``on``; yields None where off or where the program
    keeps no telemetry."""
    telemetry = None
    if on:
        try:
            from repro.core import telemetry
        except ImportError:
            pass
    if telemetry is None:
        yield None
        return
    with telemetry.recording() as rec:
        yield rec


def program_readings(rec) -> dict | None:
    """``telemetry.summary`` of a record, with ``compiles``: the number of
    root ``hls.compile`` spans, the divisor of the span metrics."""
    if rec is None:
        return None
    from repro.core import telemetry
    out = telemetry.summary(rec)
    out["compiles"] = sum(1 for s in rec.spans if s.name == "hls.compile"
                          and s.parent is None and s.end_ns is not None)
    return out


def _lowering(kernel, knee) -> dict:
    return {"mode": kernel.mode, "grid": list(kernel.grid),
            "block_rows": kernel.block_rows, "halo": kernel.halo,
            "knee": knee.desc}


def run_cell(spec: dict, cell: dict, seed: int, seconds: float, trace: bool,
             *, t0: float, cfg_override: dict | None = None,
             interpret: bool = False) -> tuple[dict, dict]:
    """One run of ``cell``; returns the result object and what the run
    learnt on the way (the lowering, set-up phases).  ``cfg_override`` and
    ``interpret`` let a test drive the run at a small size on a CPU."""
    import jax
    import numpy as np

    from bench import compare, drive, peaks
    from bench import spec as bspec

    cfg, mod = bspec.load_config(cell["config"])
    cfg = {**cfg, **(cfg_override or {})}
    mix = bspec.load_traffic(cell["traffic"])
    devices = jax.devices()[:cell["chips"]]
    ops, nbytes = mod.counts(cfg)
    nominal = mod.consts(cfg)
    deploy = mod.program(cfg, nominal)

    # a stream call runs the kernel over a batch of frames where the mix
    # names one; a recompile step runs it on one frame
    batch = mix.get("batch") if mix["kind"] == "stream" else None
    mapped = drive.varying(cfg["inputs"]) if batch else ()
    setup = drive.Spans()
    with setup("inputs"):
        frames = drive.make_inputs(deploy, cfg["inputs"], cfg["dtype"], seed,
                                   mix["distinct_inputs"], batch)
        jax.block_until_ready(frames)
    kernel, knee, f = drive.compile_once(cfg, mod, nominal, frames[0], setup,
                                         interpret, mapped)
    with setup("warmup"):
        jax.block_until_ready([f(fr) for fr in frames])
    info = {"lowering": _lowering(kernel, knee)}

    reservoir = drive.Reservoir(mix["sample"], seed)
    window = drive.Spans()
    produced = {}
    if mix["kind"] == "recompile":
        # a step that compiles the nominal program once more keeps the
        # window's first compile from paying what only a first one pays
        with setup("warm_compile"):
            drive.compile_once(cfg, mod, nominal, frames[0], drive.Spans(),
                               interpret)
    elif mix["kind"] != "stream":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    setup_s = time.perf_counter() - t0

    # a traced run of the recompile mix records the program's spans over
    # its window and its traced stretch; an untraced run never does, so
    # nothing it reports pays for them
    with _program_record(trace and mix["kind"] == "recompile") as rec:
        if mix["kind"] == "stream":
            n, secs = drive.stream(f, frames, seconds, mix["in_flight"],
                                   reservoir)
            produced["call_us"] = secs / (n * (batch or 1)) * 1e6
            attempted, failed = n * (batch or 1), 0
        else:
            rng = np.random.default_rng([seed, 1])
            with _no_cache_writes():
                n, failed, secs = drive.recompile(cfg, mod, frames[0],
                                                  seconds, rng, reservoir,
                                                  window, interpret)
            produced["compile_s"] = secs / n
            attempted = n
        produced["setup_s"] = setup_s
        info["setup_phases_s"] = {k: sum(v)
                                  for k, v in setup.durations.items()}
        info["window"] = {"attempted": attempted, "seconds": secs}

        mem = [d.memory_stats() for d in devices]
        memory_peak = max((m or {}).get("peak_bytes_in_use", 0) for m in mem)

        summary = None
        calls = 0
        if trace:
            from bench import devtrace
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            stretch = drive.Spans()
            with jax.profiler.trace(TRACE_DIR, profiler_options=opts):
                if mix["kind"] == "stream":
                    drive.stream_stretch(f, frames, mix["trace_calls"],
                                         mix["in_flight"], stretch)
                    calls = mix["trace_calls"] * (batch or 1)
                else:
                    calls = mix["trace_compiles"]
                    rng = np.random.default_rng([seed, 2])
                    with _no_cache_writes(), stretch("stretch"):
                        for _ in range(calls):
                            jax.block_until_ready(drive.edit_compile_run(
                                cfg, mod, mod.consts(cfg, rng), frames[0],
                                stretch, interpret))
            summary = devtrace.summarize(devtrace.find_xplane(TRACE_DIR),
                                         n_devices=len(devices))
    program = program_readings(rec)
    if program is not None:
        info["program_spans"] = {k: v["total_s"]
                                 for k, v in program["spans"].items()}

    # the check, once the window has closed and the peak has been read:
    # every frame of each sampled answer against the reference
    limit = cfg["limits"]["rel_err"]
    groups: dict = defaultdict(list)
    for key, out in reservoir.sample():
        consts, si = (nominal, key) if mix["kind"] == "stream" else (key, 0)
        groups[(si, json.dumps(consts, sort_keys=True))].append(
            jax.device_get(out))
    worst = 0.0
    bad = checked = 0
    for (si, consts), outs in groups.items():
        host = jax.device_get(frames[si])
        for k in range(batch or 1):
            ref = mod.reference(drive.frame_of(host, mapped, k) if batch
                                else host, json.loads(consts))
            for out in outs:
                got = {n: v[k] for n, v in out.items()} if batch else out
                err = compare.rel_err(got, ref, cfg["outputs"])
                bad += not err <= limit
                worst = max(worst, err)
                checked += 1
    correct = checked > 0 and bad == 0 and failed == 0
    info["checked_answers"] = checked

    if trace:
        readings = Readings(calls=calls, trace=summary,
                            spans=dict(window.durations), ops=ops,
                            nbytes=nbytes,
                            peak=peaks.peak_of(devices[0].device_kind),
                            program=program)
        metrics = {}
        for m in bspec.per_layer_of(spec, cell["name"]):
            v = bspec.load_reader(m["name"])(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {}
        for m in bspec.end_to_end_of(spec, cell["name"]):
            if m["name"] not in produced:
                raise ValueError(f"{cell['name']}: traffic kind "
                                 f"{mix['kind']!r} does not produce "
                                 f"{m['name']!r}")
            metrics[m["name"]] = {"value": produced[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed + bad, "metrics": metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": memory_peak}}
    if trace:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps,
                               "idle_self": summary.idle_self}
    result["checks"] = {"rel_err": {"value": worst, "limit": limit}}
    return result, info


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import compare
    from bench import spec as bspec
    spec = bspec.load_spec()
    cell = bspec.cell(spec, args.workload)
    import jax
    devices = jax.devices()
    tpus = [d for d in devices if d.platform == "tpu"]
    if len(tpus) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(tpus)} TPU(s) among "
              f"{[d.platform for d in devices]}", file=sys.stderr)
        return 2
    configure_jax_cache()
    result, info = run_cell(spec, cell, args.seed, args.seconds,
                            bool(args.trace), t0=_T0)
    print(json.dumps(info), flush=True)
    print("\n".join(compare.check_lines(result["checks"])), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
