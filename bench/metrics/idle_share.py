"""idle_share: the share of the traced stretch in which no op ran on the
device: 1 - (union of the device ops' intervals) / (stretch length).
The longest gaps are named by the host span that covers them, in the
result's ``breakdown``."""


def read(r):
    t = r.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
