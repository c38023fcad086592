"""The control of the comparison that decides ``correct``: the same cell
computed in bfloat16, the precision below the configurations' float32,
must come out as not correct.

    python3 bench/control.py --workload <name> --seeds 1 2 3 [--seconds 2]

Two controls, each read on every seed and printed as one JSON line:

* ``program``: the program's own lower-precision path, ``lower_program``
  with ``dtype="bfloat16"``, driven through a whole run of the cell (a
  short window at the cell's own load, then the run's check);
* ``reference``: the configuration's reference computed in bfloat16 with
  ``jax.numpy``, put in the program's place on the answers the window
  would check.

A control that raises reads ``inf``.  Run on a host with a TPU; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = _ROOT
    sys.path.insert(1, os.path.join(_ROOT, "src"))
    os.environ["TPU_LOG_DIR"] = "disabled"


def reference_control(cell: dict, seed: int, *, cfg_override=None) -> float:
    """rel_err of the bfloat16 reference on the inputs a run with ``seed``
    makes, for the constants its window would use."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import compare, drive
    from bench import spec as bspec

    cfg, mod = bspec.load_config(cell["config"])
    cfg = {**cfg, **(cfg_override or {})}
    mix = bspec.load_traffic(cell["traffic"])
    nominal = mod.consts(cfg)
    batch = mix.get("batch") if mix["kind"] == "stream" else None
    mapped = drive.varying(cfg["inputs"]) if batch else ()
    frames = drive.make_inputs(mod.program(cfg, nominal), cfg["inputs"],
                               cfg["dtype"], seed, mix["distinct_inputs"],
                               batch)
    if mix["kind"] == "recompile":
        rng = np.random.default_rng([seed, 1])
        cases = [(frames[0], mod.consts(cfg, rng))
                 for _ in range(mix["sample"] + 1)]
    elif batch:
        cases = [(drive.frame_of(fr, mapped, k), nominal)
                 for fr in frames for k in range(batch)]
    else:
        cases = [(fr, nominal) for fr in frames]
    worst = 0.0
    for frame, consts in cases:
        host = jax.device_get(frame)
        low = jax.jit(lambda a, c=consts: mod.reference(
            a, c, xp=jnp, dtype=jnp.bfloat16))(frame)
        worst = max(worst, compare.rel_err(jax.device_get(low),
                                           mod.reference(host, consts),
                                           cfg["outputs"]))
    return worst


def program_control(spec: dict, cell: dict, seed: int, seconds: float, *,
                    cfg_override=None, interpret: bool = False) -> float:
    """rel_err of a whole run of the cell with the program's bfloat16
    path switched on."""
    from bench import run
    result, _ = run.run_cell(
        spec, cell, seed, seconds, False, t0=time.perf_counter(),
        cfg_override={**(cfg_override or {}), "dtype": "bfloat16"},
        interpret=interpret)
    return result["checks"]["rel_err"]["value"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from bench import run
    from bench import spec as bspec
    spec = bspec.load_spec()
    cell = bspec.cell(spec, args.workload)
    import jax
    if sum(d.platform == "tpu" for d in jax.devices()) < cell["chips"]:
        print("control: no TPU", file=sys.stderr)
        return 2
    run.configure_jax_cache()
    for seed in args.seeds:
        for name, fn in (
                ("program", lambda: program_control(spec, cell, seed,
                                                    args.seconds)),
                ("reference", lambda: reference_control(cell, seed))):
            try:
                value = fn()
            except Exception:  # a control that crashes has failed
                traceback.print_exc()
                value = math.inf
            print(json.dumps({"workload": cell["name"], "seed": seed,
                              "control": name, "rel_err": value}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
