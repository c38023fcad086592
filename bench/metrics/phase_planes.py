"""phase_planes: input phase planes the generated ``run()`` slices, per
lowering: the program's counter ``hls.phase_planes`` over ``hls.lowers``.
Each holds the elements ``x[p::s, q::s]`` of an input, taken after its
edge pad, and the kernel reads it at unit stride in place of a strided
take.
None where the program keeps no such counter (a lowering counts it, 0
included) or lowered nothing."""


def read(r):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    c = telemetry.counters
    n = c.get("hls.lowers", 0)
    return c["hls.phase_planes"] / n if n and "hls.phase_planes" in c \
        else None
