"""edge_pad_bytes: bytes the generated ``run()`` writes per call to
edge-pad its inputs whole (``jnp.pad``, a full copy of each padded input),
per lowering: the program's counter ``hls.edge_pad_bytes`` over
``hls.lowers``.  None where the program keeps no such counters or lowered
nothing."""


def read(r):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    c = telemetry.counters
    n = c.get("hls.lowers", 0)
    return c.get("hls.edge_pad_bytes", 0) / n if n else None
