"""kernel_us: device time of the generated Pallas kernel per call, from
the traced stretch: the summed durations of the device ops that are
custom calls (the Mosaic kernel that ``pallas_call`` becomes), over the
calls made in the stretch.  Found by op kind, not by the kernel's name."""


def read(r):
    t = r.trace
    if t is None or not r.calls or t.kernel_s <= 0:
        return None
    return t.kernel_s / r.calls * 1e6
