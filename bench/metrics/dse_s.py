"""dse_s: mean host seconds per compile of the window spent in the DSE:
``hls.compile`` at the DSE size (lint, dependences and II,
``pareto_explore``, ``validate_static``) and the knee.  A span of the
benchmark around the call."""


def read(r):
    d = r.spans.get("dse")
    if not d:
        return None
    return sum(d) / len(d)
