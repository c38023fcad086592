"""lower_s: mean host seconds per compile of the window spent in codegen:
``lower_program`` of the knee at the deployment size.  A span of the
benchmark around the call."""


def read(r):
    d = r.spans.get("lower")
    if not d:
        return None
    return sum(d) / len(d)
