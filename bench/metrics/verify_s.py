"""verify_s: seconds per compile in the differential check of each
transformed candidate (``transforms.differential_check``); the program's
span ``hls.verify``, recorded in a traced run of the recompile mix."""
from bench.counters import span_per_compile


def read(r):
    return span_per_compile(r.program, "hls.verify")
