"""ii_probes: II probes per compile, the ``feasible`` calls of the II
search (``autotune.autotune``); the program's counter ``hls.ii_probes``."""
from bench.counters import per_compile


def read(r):
    return per_compile("hls.ii_probes")
