"""dep_ilp_cases: dependence cases per compile that the closed form could
not take and the branch-and-bound ILP solved (``deps._ilp_case_slack``);
the program's counter ``hls.dep_cases_ilp``."""
from bench.counters import per_compile


def read(r):
    return per_compile("hls.dep_cases_ilp")
