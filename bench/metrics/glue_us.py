"""glue_us: device time per call of every other device op of the traced
stretch: the XLA ops of the emitted ``run()`` around the kernel (the
``jnp.pad`` of edge rows, casts, the trim of the output)."""


def read(r):
    t = r.trace
    if t is None or not r.calls or t.kernel_s <= 0:
        return None
    return t.glue_s / r.calls * 1e6
