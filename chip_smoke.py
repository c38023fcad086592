#!/usr/bin/env python3
"""Smoke test of the compiler's main path on one TPU.

For each image pipeline the script runs the design-space exploration
(``hls.compile``) at a small size, lowers the latency x BRAM knee point at a
deployment frame size with the row block that ``emit_pallas`` maps the
knee's tile to, runs the generated Pallas kernel compiled (never
interpreted) on the chip, and checks it against the plain reference
``sim.sequential_exec`` on the same seeded inputs:

* the four fusion chains (``programs.CHAIN_BENCHMARKS``) at n = 1024 in
  float32, through the streamed lowering; a ``blur_chain`` input frame is
  1026 x 1026, about one megapixel;
* through the whole-array lowering, ``optical_flow`` at n = 256, ``dus``
  at n = 512 and ``two_mm`` (two chained n x n matmuls) at n = 128.  The
  whole-array kernel holds every array in VMEM: at twice those sizes
  ``optical_flow`` and ``two_mm`` do not fit and the chip's compiler
  refuses them (RESOURCE_EXHAUSTED).

Usage, from the repository root on a host with one TPU:

    python3 chip_smoke.py

One JSON line per program precedes the last line, which is
``{"ok": true, "device": {...}}`` naming the device as JAX reports it.
Times are host wall-clock seconds of compiler phases, not device metrics.
Without a TPU, or if any program fails, the script exits non-zero and
prints no result line.

JAX keeps its persistent compile cache in ``$JAX_COMPILATION_CACHE_DIR``
when that is set, and in ``<checkout>/.jax_cache`` otherwise.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# without the repository beside it the script fails here, before it
# touches a device
from repro.core import hls, sim  # noqa: E402
from repro.core.codegen import _point_block_rows, lower_program  # noqa: E402
from repro.core.programs import BENCHMARKS, CHAIN_BENCHMARKS  # noqa: E402

FRAME_N = 1024
# whole-array programs: name -> n
WHOLE_N = {"optical_flow": 256, "dus": 512, "two_mm": 128}
# DSE sizes: the search's differential checks grow with the iteration
# count, so it runs small and the knee is lowered at the frame size
# (the recipe of benchmarks/paper.py's codegen sweep)
DSE_N = {"blur_chain": 8, "conv_pool": 8, "gradient_harris": 6,
         "correlated_chain": 8, "optical_flow": 6, "dus": 8, "two_mm": 6}
# Elementwise |kernel - reference| <= TOL * (1 + |reference|).  The kernel
# computes in float32 (unit roundoff 6e-8) on the float32-rounded inputs
# the float64 reference also gets.  The image outputs are at most ~40
# operations deep; two_mm's are sums of 2 x 128 positive products, whose
# rounding error is under 256 roundoffs relative; optical_flow's per-pixel
# 2x2 solve divides by a cancelling determinant.  The worst error on these
# seeded inputs is 2e-7 for the chains and dus, 6.3e-7 for two_mm and
# 1.6e-6 for optical_flow (float32 on a CPU).
# A wrong window, halo or tile offset reads a neighbouring pixel of inputs
# drawn from [0.5, 2] and moves outputs by O(0.1).
TOL = 1e-4


def check_program(name: str, mk, n: int, mode: str) -> dict:
    """DSE at ``DSE_N[name]``, lower the knee at ``n``, run it compiled,
    compare with ``sim.sequential_exec``; raises on any failure."""
    t0 = time.perf_counter()
    r = hls.compile(
        mk(DSE_N[name], storage="bram"),
        objectives=(hls.minimize("latency"), hls.minimize("bram")),
        search=hls.SearchConfig(moves=("fuse", "tile"), unroll_factors=(),
                                tile_sizes=(2, 4), max_candidates=8,
                                cache=False))
    knee = r.knee("latency", "bram")
    t1 = time.perf_counter()
    p = mk(n, storage="bram")
    k = lower_program(p, block_rows=_point_block_rows(knee))
    if k.mode != mode:
        raise RuntimeError(f"{name}: lowered in mode {k.mode!r}, expected "
                           f"{mode!r} ({k.soft_reasons})")
    t2 = time.perf_counter()
    inputs = {a: v.astype(np.float32)
              for a, v in sim.make_inputs(p, seed=0).items()}
    compiled = jax.jit(k.fn).lower(inputs).compile()
    t3 = time.perf_counter()
    out = jax.block_until_ready(compiled(inputs))
    t4 = time.perf_counter()
    ref = sim.sequential_exec(
        p, {a: v.astype(np.float64) for a, v in inputs.items()})
    t5 = time.perf_counter()
    abs_err = scaled_err = 0.0
    for a in k.outputs:
        got = np.asarray(out[a], np.float64)
        if got.shape != p.arrays[a].shape or not np.isfinite(got).all():
            raise RuntimeError(f"{name}: output '{a}' has shape {got.shape} "
                               f"(expected {p.arrays[a].shape}) or is not "
                               "finite")
        err = np.abs(got - ref[a])
        abs_err = max(abs_err, float(err.max()))
        scaled_err = max(scaled_err,
                         float((err / (1.0 + np.abs(ref[a]))).max()))
    rec = {"program": name, "n": n, "dtype": "float32", "mode": k.mode,
           "grid": list(k.grid), "block_rows": k.block_rows, "halo": k.halo,
           "knee": knee.desc, "max_abs_err": abs_err,
           "max_scaled_err": scaled_err, "tol": TOL,
           "host_dse_s": t1 - t0, "host_lower_s": t2 - t1,
           "host_jit_compile_s": t3 - t2, "host_reference_s": t5 - t4}
    if not scaled_err <= TOL:
        raise RuntimeError(f"{name}: max scaled error {scaled_err:.3e} > "
                           f"{TOL:g} against sequential_exec: {rec}")
    return rec


def smoke() -> list[str]:
    """Check every program; print one JSON line each, return the names of
    the programs that failed."""
    cases = [(name, mk, FRAME_N, "streamed")
             for name, mk in CHAIN_BENCHMARKS.items()]
    cases += [(name, BENCHMARKS[name], n, "whole")
              for name, n in WHOLE_N.items()]
    failed = []
    for name, mk, n, mode in cases:
        try:
            rec = check_program(name, mk, n, mode)
        except Exception as e:  # report every program, then fail the run
            traceback.print_exc()
            failed.append(name)
            rec = {"program": name, "n": n, "error": f"{type(e).__name__}: "
                   f"{str(e).splitlines()[0] if str(e) else ''}"}
        print(json.dumps(rec), flush=True)
    return failed


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); the kernels "
              "run compiled only on a TPU", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed path: the directory is part of the cache key
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    failed = smoke()
    if failed:
        print(f"chip_smoke: failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
