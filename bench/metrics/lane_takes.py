"""lane_takes: lane-axis strided takes in the generated kernel's body, per
lowering: the program's counter ``hls.lane_takes`` over ``hls.lowers``.
Each such ``_take`` is a transpose, a reshape and a transpose back in every
grid step of the kernel.  None where the program keeps no such counters
or lowered nothing."""


def read(r):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    c = telemetry.counters
    n = c.get("hls.lowers", 0)
    return c.get("hls.lane_takes", 0) / n if n else None
