"""The program's own counters and spans (``repro.core.telemetry``), read
by the per-layer metrics of the recompile cell.

The counters are process-wide and always on, so a reader sees every
compile the run made: set-up, window and traced stretch.  Each compile of
a cell does the same search (new weights change values, not the program's
structure), so the mean per compile is each compile's count.

Spans are kept only inside ``telemetry.recording()``, which a traced run
of the recompile mix holds around its window and its traced stretch
(``Readings.program``): a span's total per recorded compile is the mean
time of that phase in a compile.
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


def span_per_compile(program, name: str):
    """Seconds in span ``name`` per recorded ``hls.compile`` root, from a
    run's ``Readings.program``; 0.0 where the span never opened in those
    compiles, None where nothing was recorded or no compile was."""
    if not program or not program["compiles"]:
        return None
    return program["spans"].get(name, {}).get("total_s", 0.0) \
        / program["compiles"]
