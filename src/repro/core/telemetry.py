"""In-program spans and counters: where a compile spends its time.

* ``count(name, n=1)`` adds to a process-wide counter in ``counters``.
  Counters are always on; each is one dict add.
* ``span(name, **attrs)`` is a context manager around one phase.  Outside
  ``recording()`` it is one flag check and returns a shared no-op.  Inside,
  it keeps the span in memory (id, parent id, root id, name, start and end
  on ``time.perf_counter_ns``, attributes) and enters
  ``jax.profiler.TraceAnnotation(name)``, so under ``jax.profiler.trace``
  the span lies on the profiler's clock beside the device ops.
* ``recording()`` turns span recording on for its body and yields a
  ``Record``: the spans, and each counter's change over the body.
* ``summary(record)`` gives per span name its count, total and self
  seconds (self: the duration less what child spans cover), and per
  counter its change.

A span's root is the outermost span open around it (itself at the top):
for the phases of a compile, its ``hls.compile``.  Spans nest in the order
they open, so one thread records at a time.  JAX is imported only when a
recording starts.  DESIGN.md §6 lists the span and counter names.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional

counters: dict[str, int] = {}

_on = False
_record: Optional["Record"] = None
_open: list["Span"] = []   # spans open now, outermost first
_trace_annotation = None   # jax.profiler.TraceAnnotation, once recording


def count(name: str, n: int = 1) -> None:
    counters[name] = counters.get(name, 0) + n


@dataclass
class Record:
    spans: list["Span"] = field(default_factory=list)  # in opening order
    counters: dict[str, int] = field(default_factory=dict)


class Span:
    __slots__ = ("id", "parent", "root", "name", "attrs", "start_ns",
                 "end_ns", "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.end_ns: Optional[int] = None

    def set(self, **attrs) -> None:
        """Attributes learnt inside the span (e.g. a cache hit)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        up = _open[-1] if _open else None
        self.id = len(_record.spans) + 1
        self.parent = up.id if up else None
        self.root = up.root if up else self.id
        _record.spans.append(self)
        _open.append(self)
        self._annotation = _trace_annotation(self.name)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        _open.pop()
        return False


class _Off:
    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


def span(name: str, /, **attrs):
    """A span named ``name`` around a ``with`` body (see the module)."""
    if not _on:
        return _OFF
    return Span(name, attrs)


@contextmanager
def recording():
    """Record spans for the body; yields the ``Record``, whose counters
    are filled in when the body ends."""
    global _on, _record, _trace_annotation
    if _on:
        raise RuntimeError("telemetry.recording() is already on")
    from jax.profiler import TraceAnnotation
    _trace_annotation = TraceAnnotation
    rec = Record()
    before = dict(counters)
    _record, _on = rec, True
    try:
        yield rec
    finally:
        _on, _record = False, None
        _open.clear()
        rec.counters = {k: v - before.get(k, 0) for k, v in counters.items()
                        if v != before.get(k, 0)}


def summary(record: Record) -> dict:
    """``{"spans": {name: {"count", "total_s", "self_s"}}, "counters":
    {name: change}}`` of the record's finished spans."""
    done = [s for s in record.spans if s.end_ns is not None]
    covered: dict[int, int] = {}
    for s in done:
        if s.parent is not None:
            covered[s.parent] = covered.get(s.parent, 0) + s.end_ns - s.start_ns
    spans: dict[str, dict] = {}
    for s in done:
        d = s.end_ns - s.start_ns
        e = spans.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
        e["count"] += 1
        e["total_s"] += d * 1e-9
        e["self_s"] += (d - covered.get(s.id, 0)) * 1e-9
    return {"spans": spans, "counters": dict(record.counters)}
