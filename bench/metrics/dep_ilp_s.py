"""dep_ilp_s: seconds per compile in the dependence cases that the closed
form could not take, solved by branch and bound
(``deps._ilp_case_slack``), most of it inside the II search; the
program's span ``hls.dep_ilp``, recorded in a traced run of the
recompile mix."""
from bench.counters import span_per_compile


def read(r):
    return span_per_compile(r.program, "hls.dep_ilp")
