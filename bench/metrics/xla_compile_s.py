"""xla_compile_s: mean host seconds per compile of the window spent in XLA
and Mosaic: ``jax.jit(kernel.fn).lower(inputs).compile()``.  A span of the
benchmark around the call."""


def read(r):
    d = r.spans.get("xla_compile")
    if not d:
        return None
    return sum(d) / len(d)
