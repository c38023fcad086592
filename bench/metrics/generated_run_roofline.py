"""generated_run_roofline: the least time the chip could take for one
call's work, as a share of the device time the generated code (kernel plus
glue) took per call.  The work is the algorithm's, counted from the
configuration's shapes (its ``counts``), never the kernel's own traffic;
the least time is the larger of operations over the peak and bytes over
HBM bandwidth (``bench/peaks.py``)."""
from bench.peaks import least_time_s


def read(r):
    t = r.trace
    if t is None or not r.calls:
        return None
    busy = (t.kernel_s + t.glue_s) / r.calls
    if busy <= 0:
        return None
    least, _ = least_time_s(r.ops, r.nbytes, r.peak)
    return 100.0 * least / busy
