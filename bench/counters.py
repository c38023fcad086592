"""The program's own counters (``repro.core.telemetry``), read by the
per-layer metrics of the recompile cell.

The counters are process-wide and always on, so a reader sees every
compile the run made: set-up, window and traced stretch.  Each compile of
a cell does the same search (new weights change values, not the program's
structure), so the mean per compile is each compile's count.
"""


def per_compile(name: str):
    """Counter ``name`` over the counter ``hls.compiles``; None where the
    program keeps no such counters or compiled nothing."""
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    c = telemetry.counters
    n = c.get("hls.compiles", 0)
    return c.get(name, 0) / n if n else None
