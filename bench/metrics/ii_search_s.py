"""ii_search_s: seconds per compile in the II search
(``autotune.autotune``: a binary search per loop, each probe a
``feasible`` call); the program's span ``hls.ii_search``, recorded in a
traced run of the recompile mix."""
from bench.counters import span_per_compile


def read(r):
    return span_per_compile(r.program, "hls.ii_search")
