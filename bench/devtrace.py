"""Reduction of a profiler trace to the device numbers of a traced stretch.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Two kinds of events matter:

* device ops: the events of the ``XLA Ops`` line of each TPU plane
  (``/device:TPU:<n>``).  An op is the generated kernel when its HLO
  opcode is ``custom-call`` with the target ``tpu_custom_call`` (the
  Mosaic kernel that ``pallas_call`` becomes), found by op kind and not by
  the kernel's name; every other op is glue.  An op whose event holds
  others (a ``while`` loop's) is counted through the ops it holds.
* host spans: the benchmark's own annotations, named ``bench.<what>``, and
  the program's, named ``hls.<what>`` (``repro.core.telemetry``, recorded
  only inside ``telemetry.recording()``), on the host plane.
  ``bench.stretch`` bounds the traced stretch.  The ``bench.*`` spans name
  the longest idle gaps of the device (``idle_gaps``); all of them, the
  innermost open one, split its whole idle time (``idle_self``).

The profiler puts both on one clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
STRETCH = "bench.stretch"
HOST_SPANS = ("bench.", "hls.")
OUTSIDE = "host outside bench spans"
TOP = 10


@dataclass
class Op:
    device: int
    name: str
    start_ns: float
    dur_ns: float
    kernel: bool


@dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float


@dataclass
class Summary:
    kernel_s: float        # kernel op time in the stretch, mean over chips
    glue_s: float          # other op time in the stretch, mean over chips
    busy_s: float          # union of op intervals, mean over chips
    window_s: float        # length of the stretch
    device_ops: list = field(default_factory=list)  # [[name, s]] top 10
    idle_gaps: list = field(default_factory=list)   # [[host span, s]]
    idle_self: list = field(default_factory=list)   # [[host span, s]] top 10


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    return paths[0]


# An op's event name on a TPU is its HLO instruction, e.g.
# ``%copy = f32[1082,1922]{1,0:T(8,128)S(1)} copy(f32[...] %arg)``: the
# opcode is the first lower-case word followed by "(" (the layout's
# ``T(8,128)`` and ``S(1)`` are upper-case).
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def op_kind(name: str) -> str:
    """The HLO opcode of a device op's event name (the name itself where
    it is not an HLO instruction)."""
    m = _OPCODE.search(name)
    return m.group(1) if m else name


_MOSAIC = 'custom_call_target="tpu_custom_call"'


def is_kernel(name: str) -> bool:
    """Whether a device op is a Mosaic kernel: a ``custom-call`` whose
    target is ``tpu_custom_call``, not another custom call such as XLA's
    ``AllocateBuffer``."""
    return op_kind(name) == "custom-call" and _MOSAIC in name


def short_name(name: str) -> str:
    """``<opcode> <instruction>``, e.g. ``copy %copy``."""
    lhs = name.split(" = ", 1)[0] if " = " in name else ""
    return f"{op_kind(name)} {lhs}".strip()


def load(path: str) -> tuple[list[Op], list[Span]]:
    """The device ops and the host spans (the benchmark's and the
    program's) of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: list[Op] = []
    spans: list[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                for ev in line.events:
                    ops.append(Op(int(m.group(1)), short_name(ev.name),
                                  ev.start_ns, ev.duration_ns,
                                  is_kernel(ev.name)))
            elif not m and plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(HOST_SPANS):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.duration_ns))
    return ops, spans


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(ops: list[Op]) -> list[Op]:
    """The ops that hold no other op of their device.  A control-flow op
    (the ``while`` of ``lax.map``) is an event that spans the ops of its
    body; counting it too would count their time twice."""
    out = []
    order = sorted(ops, key=lambda o: (o.device, o.start_ns, -o.dur_ns))
    for i, o in enumerate(order):
        end = o.start_ns + o.dur_ns
        holds = False
        for p in order[i + 1:]:
            if p.device != o.device or p.start_ns >= end:
                break
            if p.start_ns + p.dur_ns <= end:
                holds = True
                break
        if not holds:
            out.append(o)
    return out


def reduce(ops: list[Op], spans: list[Span], n_devices: int) -> Summary:
    """Kernel, glue and busy time of the ops inside the stretch, the
    stretch's length, the ops that took most time, the longest idle gaps,
    each named by the ``bench.*`` span that overlaps it most, and the idle
    time by the innermost host span open over it (``idle_self``).  Kernel
    and glue time count only the ops that hold no other (``leaves``)."""
    st = [s for s in spans if s.name == STRETCH]
    if len(st) != 1:
        raise ValueError(f"expected one {STRETCH} span, found {len(st)}")
    w0, w1 = st[0].start_ns, st[0].start_ns + st[0].dur_ns
    inside = [o for o in ops if o.start_ns >= w0 and o.start_ns + o.dur_ns <= w1]
    work = leaves(inside)
    kernel = sum(o.dur_ns for o in work if o.kernel)
    glue = sum(o.dur_ns for o in work if not o.kernel)
    per_op: dict[str, float] = {}
    for o in work:
        per_op[o.name] = per_op.get(o.name, 0.0) + o.dur_ns
    busy = 0.0
    gaps: list[tuple[float, float, float]] = []
    for dev in sorted({o.device for o in inside}):
        u = _union([(o.start_ns, o.start_ns + o.dur_ns)
                    for o in inside if o.device == dev])
        busy += sum(e - s for s, e in u)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps += [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    host = [s for s in spans if s.name != STRETCH]
    ours = [s for s in host if s.name.startswith("bench.")]
    named = []
    for length, g0, g1 in sorted(gaps, key=lambda g: (-g[0], g[1]))[:TOP]:
        best, cover = OUTSIDE, 0.0
        for s in ours:
            c = min(g1, s.start_ns + s.dur_ns) - max(g0, s.start_ns)
            if c > cover:
                best, cover = s.name, c
        named.append([best, length * 1e-9])
    n = max(n_devices, 1)
    by_span = idle_by_innermost(gaps, host)
    return Summary(
        kernel_s=kernel * 1e-9 / n, glue_s=glue * 1e-9 / n,
        busy_s=busy * 1e-9 / n, window_s=(w1 - w0) * 1e-9,
        device_ops=[[k, v * 1e-9] for k, v in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=named,
        idle_self=[[k, v * 1e-9 / n] for k, v in
                   sorted(by_span.items(), key=lambda kv: -kv[1])[:TOP]])


def idle_by_innermost(gaps: list[tuple[float, float, float]],
                      spans: list[Span]) -> dict[str, float]:
    """The idle time of ``gaps`` ((length, start, end), ns) by the
    innermost of ``spans`` open over it: of the spans that cover an
    instant, the one opened last (the shorter at a tie).  Time that no
    span covers goes to ``OUTSIDE``."""
    edges = sorted({x for s in spans for x in (s.start_ns,
                                                s.start_ns + s.dur_ns)})
    out: dict[str, float] = {}
    for _, g0, g1 in gaps:
        cuts = ([g0] + edges[bisect.bisect_right(edges, g0):
                             bisect.bisect_left(edges, g1)] + [g1])
        for a, b in zip(cuts, cuts[1:]):
            open_ = [s for s in spans
                     if s.start_ns <= a and b <= s.start_ns + s.dur_ns]
            name = (max(open_, key=lambda s: (s.start_ns, -s.dur_ns)).name
                    if open_ else OUTSIDE)
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def summarize(path: str, n_devices: int) -> Summary:
    ops, spans = load(path)
    return reduce(ops, spans, n_devices)
