"""Pallas codegen backend: lower a scheduled ``Program`` to a real kernel.

This closes the modeled-vs-measured loop named in ROADMAP: after PRs 1-7 the
DSE winner was a latency *number*; this module turns the winning design point
into an executable Pallas kernel so ``benchmarks/run.py codegen`` can record
measured wall-clock next to the modeled latency (BENCH_codegen.json).

Two lowering strategies (DESIGN.md §10):

* **streamed** ("Mode A") — for single-sink producer-consumer chains of
  perfect depth-2 nests (the paper's Fig. 1 shape).  The sink's row loop is
  strip-mined into a 1-D Pallas grid of ``T = ceil(Rout/block_rows)`` steps;
  every producer stage is recomputed per grid step over exactly the *window*
  of its rows the later stages consume.  Windows are derived by propagating
  ``rows [a*t+b, a*t+b+sz)`` triples backward through the chain, which
  generalizes the shift-and-peel fusion analysis: a producer's window
  overhang ``sz - a`` IS the fusion's row shift (the VMEM line-buffer halo)
  whenever the DSE fused that edge.  Intermediates live entirely in
  registers/VMEM — they never materialize in HBM.

  - ``buffering="double"`` emits the window reads against whole-array input
    refs inside a gridded ``pallas_call`` with a per-tile output BlockSpec:
    Pallas' grid pipeline machinery ping-pongs the output block buffers, so
    tile ``t+1``'s refill overlaps tile ``t``'s compute.
  - ``buffering="single"`` emits the same stage body inside a
    ``lax.fori_loop`` over tiles with an explicit ref store of each tile —
    one window, serialized refill/compute/store.  It exists as the
    measurable baseline the double-buffered variant must beat.

* **whole-array** ("Mode B") — the generic fallback for programs the
  streamed contract rejects only *softly* (multi-store nests, strided or
  transposed stores, reads of unwritten regions, multiple sinks,
  reduction-carrying nests): every array becomes a whole VMEM ref, each
  nest is vectorized over its full domain in program order, and partial
  stores update a value initialized from the ref (so uncovered elements
  keep their initial values, exactly like ``sim.sequential_exec``).
  Canonical accumulations — the innermost iv absent from the store index,
  every load of the stored array at the store address (``two_mm``-style
  matmuls) — vectorize the outer ivs and fold the innermost one with a
  left fold unrolled at trace time, which matches the sequential float
  rounding bit for bit.

Programs outside both contracts (multi-chain tasks, imperfect nests, loose
top-level ops, non-canonical reductions — the shape vocabulary is
``ir.nest_shape``) raise the structured :class:`UnlowerableProgram`
carrying machine-readable :class:`NestContractViolation` entries instead
of an opaque downstream failure; ``CompileResult.emit_pallas`` records the
rejection (with its violation codes) in ``diagnostics``.

The kernel is emitted as *source text* and ``exec``'d: the source is the
debuggable artifact (``PallasKernel.source``), and the golden test asserts
the generated blur-chain kernel is bit-exact against the hand-written
``kernels/stencil_pipeline.py`` it generalizes.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field, replace
from typing import Callable, Optional

from . import telemetry
from .errors import NestContractViolation, UnlowerableProgram
from .ir import (AffExpr, ArithOp, ConstOp, LoadOp, Loop, Program, StoreOp,
                 nest_shape)

# Rows of one f32 (8, 128) vreg tile on a TPU: the chip's compiler accepts a
# gridded block only when its second-to-last dim is a multiple of this or
# spans the whole array.  Also the row block when none is given.
SUBLANE_ROWS = 8

_ARITH_FMT = {
    "add": "({} + {})",
    "sub": "({} - {})",
    "mul": "({} * {})",
    "div": "({} / {})",
    "min": "jnp.minimum({}, {})",
    "max": "jnp.maximum({}, {})",
    "cmp": "({} > {}).astype(DTYPE)",
}


def _ident(name: str) -> str:
    return re.sub(r"\W", "_", name)


def _vname(ssa: str) -> str:
    return "v_" + _ident(ssa.lstrip("%"))


# ---------------------------------------------------------------------------
# Nest extraction + the hard (mode-independent) contract
# ---------------------------------------------------------------------------


@dataclass
class _Access:
    """One affine access, separability-checked: per array dim at most one
    induction variable, ``coef * iv + const`` with coef >= 1, const >= 0."""

    array: str
    dims: list[tuple[Optional[str], int, int]]  # (iv | None, coef, const)


@dataclass
class _Nest:
    loop: Loop
    ivs: list[str]
    trips: list[int]
    ops: list  # innermost body, program order
    loads: list[tuple[LoadOp, _Access]] = field(default_factory=list)
    stores: list[tuple[StoreOp, _Access]] = field(default_factory=list)
    # reduction carry (canonical accumulation): the innermost iv is absent
    # from the store index, and every load of the stored array matches the
    # store address exactly — ``dst[outs] = f(dst[outs], inputs[.., red..])``
    red_iv: Optional[str] = None
    red_loads: tuple = ()  # uids of the carried-accumulator loads


def _hard(hard: list, code: str, detail: str) -> None:
    hard.append(NestContractViolation(code, "codegen", detail))


def _classify_access(nest_ivs, index, arr_shape, what, tag, hard):
    dims = []
    seen_ivs: set = set()
    if len(index) != len(arr_shape):
        _hard(hard, "rank-mismatch",
              f"nest '{tag}': {what} rank {len(index)} != array rank "
              f"{len(arr_shape)}")
        return None
    if len(arr_shape) > 2:
        _hard(hard, "rank",
              f"nest '{tag}': {what} of a rank-{len(arr_shape)} array "
              "(only 1-D/2-D arrays lower)")
        return None
    for e in index:
        e = e if isinstance(e, AffExpr) else AffExpr({}, int(e))
        if len(e.coeffs) > 1:
            _hard(hard, "non-separable",
                  f"nest '{tag}': non-separable {what} index {e!r}")
            return None
        if e.const < 0:
            _hard(hard, "negative-offset",
                  f"nest '{tag}': negative {what} offset {e!r}")
            return None
        if e.coeffs:
            (ivn, coef), = e.coeffs.items()
            if ivn not in nest_ivs:
                _hard(hard, "unknown-iv",
                      f"nest '{tag}': {what} uses unknown iv '{ivn}'")
                return None
            if coef < 1:
                _hard(hard, "negative-stride",
                      f"nest '{tag}': negative-stride {what} {e!r}")
                return None
            if ivn in seen_ivs:
                _hard(hard, "diagonal-access",
                      f"nest '{tag}': iv '{ivn}' in two {what} dims "
                      "(diagonal access)")
                return None
            seen_ivs.add(ivn)
            dims.append((ivn, coef, e.const))
        else:
            dims.append((None, 0, e.const))
    return dims


def _extract_nests(p: Program) -> tuple[list[_Nest], list]:
    hard: list = []
    nests: list[_Nest] = []
    shape = nest_shape(p)
    for ti, item in enumerate(p.body):
        ts = shape.task(ti)
        # one contract check, one place: the structural gate is the
        # ir.nest_shape classifier, not an ad-hoc re-traversal
        if ts.kind == "ops":
            _hard(hard, "top-level-ops",
                  "top-level op outside any loop nest "
                  "(run transforms.Normalize to sink loose ops)")
            continue
        if ts.kind == "imperfect":
            _hard(hard, "imperfect-nest",
                  f"nest '{item.ivname}': imperfect nest (ops mixed with an "
                  "inner loop; run transforms.Normalize to sink them)")
            continue
        if ts.kind == "multi_loop":
            _hard(hard, "multi-chain",
                  f"nest '{item.ivname}': multiple inner loops at one level "
                  "(multi-chain tasks have no single vectorized domain)")
            continue
        ivs, trips, cur = [], [], item
        ops, chain_ok = None, True
        while True:
            if cur.lb != 0:
                _hard(hard, "non-zero-lb",
                      f"nest '{item.ivname}': non-zero lower bound")
                chain_ok = False
                break
            ivs.append(cur.ivname)
            trips.append(cur.trip)
            inner = [x for x in cur.body if isinstance(x, Loop)]
            if inner:
                cur = inner[0]
                continue
            ops = cur.body
            break
        if not chain_ok:
            continue
        nest = _Nest(loop=item, ivs=ivs, trips=trips, ops=ops)
        ok = True
        red_stores = []  # stores whose index omits the innermost iv
        for op in ops:
            if isinstance(op, LoadOp):
                dims = _classify_access(set(ivs), op.index,
                                        p.arrays[op.array].shape, "load",
                                        item.ivname, hard)
                if dims is None:
                    ok = False
                    break
                nest.loads.append((op, _Access(op.array, dims)))
            elif isinstance(op, StoreOp):
                dims = _classify_access(set(ivs), op.index,
                                        p.arrays[op.array].shape, "store",
                                        item.ivname, hard)
                if dims is None:
                    ok = False
                    break
                used = [d[0] for d in dims if d[0] is not None]
                if (sorted(used) == sorted(ivs[:-1]) and len(ivs) >= 2
                        and len(used) == len(dims)):
                    # reduction-carrying store: every iv but the innermost
                    red_stores.append(op)
                elif sorted(used) != sorted(ivs) or len(used) != len(dims):
                    _hard(hard, "store-shape",
                          f"nest '{item.ivname}': store to '{op.array}' "
                          "must use every nest iv (or every iv but the "
                          "innermost reduction iv) in exactly one dim "
                          "(no constant dims)")
                    ok = False
                    break
                nest.stores.append((op, _Access(op.array, dims)))
            elif isinstance(op, ArithOp):
                if op.fn not in _ARITH_FMT:
                    _hard(hard, "unsupported-op",
                          f"nest '{item.ivname}': unsupported op '{op.fn}'")
                    ok = False
                    break
            elif not isinstance(op, ConstOp):
                _hard(hard, "unsupported-node",
                      f"nest '{item.ivname}': unsupported IR node "
                      f"{type(op).__name__}")
                ok = False
                break
        if not ok:
            continue
        if red_stores:
            # canonical accumulation: ONE reduction store, and every load
            # of the carried array matches the store address exactly, so
            # the nest is a left fold over the innermost iv —
            # dst[outs] = f(dst[outs], inputs[.., red, ..]) per step
            if len(nest.stores) != 1:
                _hard(hard, "reduction",
                      f"nest '{item.ivname}': reduction with "
                      f"{len(nest.stores)} stores (only single-store "
                      "accumulations lower)")
                continue
            sop, sacc = nest.stores[0]
            carried = [(op_, a) for op_, a in nest.loads
                       if a.array == sacc.array]
            if not carried or any(a.dims != sacc.dims for _, a in carried):
                _hard(hard, "reduction",
                      f"nest '{item.ivname}': reduction — reads "
                      f"'{sacc.array}' it also writes at a different "
                      "address (non-canonical carried accumulation)")
                continue
            nest.red_iv = ivs[-1]
            nest.red_loads = tuple(op_.uid for op_, _ in carried)
        rd = {a.array for _, a in nest.loads}
        wr = {a.array for _, a in nest.stores}
        for arr in sorted(rd & wr):
            if nest.red_iv is not None and arr == nest.stores[0][1].array:
                continue  # the canonical carry, handled above
            _hard(hard, "reduction",
                  f"nest '{item.ivname}': reduction — reads '{arr}' it "
                  "also writes (carried accumulation outside the "
                  "canonical innermost-axis pattern has no lowering)")
            ok = False
        if ok:
            nests.append(nest)
    writers: dict[str, str] = {}
    for nest in nests:
        for _, acc in nest.stores:
            prev = writers.get(acc.array)
            if prev is not None and prev != nest.loop.ivname:
                _hard(hard, "multi-writer",
                      f"array '{acc.array}' written by two nests "
                      f"('{prev}', '{nest.loop.ivname}')")
            writers[acc.array] = nest.loop.ivname
    return nests, hard


# ---------------------------------------------------------------------------
# Mode A: the streamed (grid + window) plan
# ---------------------------------------------------------------------------


@dataclass
class _StagePlan:
    nest: _Nest
    out: str                    # produced array, or one phase copy of it
    r0: int                     # store row/col offsets into the array
    c0: int
    win_a: int = 0              # domain rows [a*t+b, a*t+b+sz) per grid step
    win_b: int = 0
    win_sz: int = 0


@dataclass
class _StreamPlan:
    stages: list[_StagePlan]
    sink: _StagePlan
    block_rows: int
    grid: int                   # T
    inputs: list[str]           # arrays read from refs (not stage-produced)
    pad_rows: dict[str, int]    # input array -> trailing edge-pad rows
    halo: dict[str, int]        # produced array -> window overhang (sz - a)
    # phase copy or input plane -> (array, (row, col) strides, phases)
    phases: dict[str, tuple]


def _plan_streamed(p: Program, nests: list[_Nest],
                   block_rows: int) -> tuple[Optional[_StreamPlan], list[str]]:
    soft: list[str] = []
    stages: list[_StagePlan] = []
    for nest in nests:
        tag = nest.loop.ivname
        if nest.red_iv is not None:
            soft.append(f"nest '{tag}': streamed mode does not pipeline "
                        "reduction-carrying nests (whole-array fallback)")
            return None, soft
        if len(nest.ivs) != 2:
            soft.append(f"nest '{tag}': streamed mode needs depth-2 nests")
            return None, soft
        if len(nest.stores) != 1:
            soft.append(f"nest '{tag}': streamed mode needs exactly one "
                        f"store ({len(nest.stores)} found)")
            return None, soft
        _, acc = nest.stores[0]
        (iv0, c0_, r_off), (iv1, c1_, c_off) = acc.dims
        if (iv0, c0_) != (nest.ivs[0], 1) or (iv1, c1_) != (nest.ivs[1], 1):
            soft.append(f"nest '{tag}': store to '{acc.array}' is strided or "
                        "transposed")
            return None, soft
        stages.append(_StagePlan(nest=nest, out=acc.array, r0=r_off,
                                 c0=c_off))
    if not stages:
        soft.append("no loop nests")
        return None, soft
    produced = {s.out: i for i, s in enumerate(stages)}
    # loads: row dim must carry the outer iv; col dim the inner iv or const
    for si, s in enumerate(stages):
        tag = s.nest.loop.ivname
        for _, acc in s.nest.loads:
            if len(acc.dims) != 2:
                soft.append(f"nest '{tag}': streamed mode needs 2-D loads "
                            f"('{acc.array}' is {len(acc.dims)}-D)")
                return None, soft
            (riv, _, _), (civ, _, _) = acc.dims
            if riv != s.nest.ivs[0] or civ not in (s.nest.ivs[1], None):
                soft.append(f"nest '{tag}': load of '{acc.array}' is "
                            "transposed or row-constant")
                return None, soft
            if acc.array in produced and produced[acc.array] >= si:
                soft.append(f"nest '{tag}': reads '{acc.array}' before its "
                            "producer runs (initial-value read)")
                return None, soft
    sinks = [s for s in stages
             if not any(acc.array == s.out
                        for t in stages for _, acc in t.nest.loads)]
    if len(sinks) != 1:
        soft.append("streamed mode needs a unique sink stage "
                    f"({len(sinks)} found: {[s.out for s in sinks]})")
        return None, soft
    sink = sinks[0]
    shape = p.arrays[sink.out].shape
    if (sink.r0, sink.c0) != (0, 0) or tuple(sink.nest.trips) != shape:
        soft.append(f"sink nest '{sink.nest.loop.ivname}' does not fully "
                    f"cover '{sink.out}'")
        return None, soft
    # coverage: every stage-to-stage read stays inside the producer's
    # written box (else the read would see initial values -> Mode B)
    for s in stages:
        for _, acc in s.nest.loads:
            if acc.array not in produced:
                continue
            prod = stages[produced[acc.array]]
            (riv, rc, rk), (civ, cc, ck) = acc.dims
            rmax = rc * (s.nest.trips[0] - 1) + rk
            cmax = (cc * (s.nest.trips[1] - 1) + ck) if civ else ck
            if not (rk >= prod.r0 and ck >= prod.c0
                    and rmax < prod.r0 + prod.nest.trips[0]
                    and cmax < prod.c0 + prod.nest.trips[1]):
                soft.append(f"nest '{s.nest.loop.ivname}': load of "
                            f"'{acc.array}' reads outside the producer's "
                            "written box")
                return None, soft
    assert stages[-1] is sink  # a later stage would be read, or a sink
    stages, phases = _split_phases(stages)
    sink = stages[-1]
    produced = {s.out: i for i, s in enumerate(stages)}
    # backward window propagation: sink computes [B*t, B*t+B)
    rout = sink.nest.trips[0]
    B = max(1, min(block_rows, rout))
    sink.win_a, sink.win_b, sink.win_sz = B, 0, B
    halo: dict[str, int] = {}
    for s in reversed(stages):
        if s is sink:
            continue
        reqs = []  # (consumer stage, row coef, row const)
        for c in stages:
            for _, acc in c.nest.loads:
                if acc.array == s.out:
                    reqs.append((c, acc.dims[0][1], acc.dims[0][2]))
        # the unique-sink check already ran: a non-sink stage has consumers,
        # and they are later stages whose windows are already resolved
        assert reqs, s.out
        rates = {rc * c.win_a for c, rc, _ in reqs}
        if len(rates) > 1:
            soft.append(f"consumers of '{s.out}' advance at incompatible "
                        f"row rates {sorted(rates)}")
            return None, soft
        a = rates.pop()
        lo = min(rc * c.win_b + rk for c, rc, rk in reqs) - s.r0
        hi = max(rc * (c.win_b + c.win_sz - 1) + rk
                 for c, rc, rk in reqs) - s.r0
        if lo < 0:
            soft.append(f"window of '{s.out}' starts before its domain "
                        f"(offset {lo})")
            return None, soft
        s.win_a, s.win_b, s.win_sz = a, lo, hi - lo + 1
        arr = phases.get(s.out, (s.out,))[0]
        halo[arr] = max(halo.get(arr, s.win_sz - s.win_a),
                        s.win_sz - s.win_a)
    T = -(-rout // B)
    # trailing edge-padding so the last (possibly partial) tile's input
    # reads stay in bounds; padded rows only feed output rows >= Rout,
    # which the host wrapper trims
    pad_rows: dict[str, int] = {}
    inputs: list[str] = []
    for s in stages:
        for _, acc in s.nest.loads:
            if acc.array in produced:
                continue
            if acc.array not in inputs:
                inputs.append(acc.array)
            rc, rk = acc.dims[0][1], acc.dims[0][2]
            need = rc * (s.win_a * (T - 1) + s.win_b + s.win_sz - 1) + rk
            # a plane's row i is its array's row stride * i + phase
            arr, (st, _), (u, _) = phases.get(acc.array,
                                              (acc.array, (1, 1), (0, 0)))
            over = st * need + u - (p.arrays[arr].shape[0] - 1)
            if over > 0:
                pad_rows[arr] = max(pad_rows.get(arr, 0), over)
    return _StreamPlan(stages=stages, sink=sink, block_rows=B, grid=T,
                       inputs=inputs, pad_rows=pad_rows, halo=halo,
                       phases=phases), soft


def _phase_copy(s: _StagePlan, out: str, stride: tuple,
                phase: tuple) -> _StagePlan:
    """Stage ``s`` evaluated where its array's (row, col) index is
    ``stride * i + phase``, as a stage writing ``out[i]``: the stride is
    folded into the coefficients and offsets of its own reads."""
    n = s.nest
    off = (s.r0, s.c0)
    # the first and last i whose domain index lies in the stage's domain
    lo = [-((u - o) // st) for u, o, st in zip(phase, off, stride)]
    hi = [(o + trip - 1 - u) // st
          for u, o, st, trip in zip(phase, off, stride, n.trips)]
    base = [st * i + u - o for i, u, o, st in zip(lo, phase, off, stride)]
    loads = [(op, _Access(acc.array, [(iv, k * st, k * b + c) for
                                      (iv, k, c), st, b in
                                      zip(acc.dims, stride, base)]))
             for op, acc in n.loads]
    nest = replace(n, trips=[h - i + 1 for i, h in zip(lo, hi)], loads=loads)
    return _StagePlan(nest=nest, out=out, r0=lo[0], c0=lo[1])


def _split_phases(stages: list[_StagePlan]) -> tuple[list[_StagePlan], dict]:
    """Polyphase lowering of strided reads.  Where every read of an array
    along an axis has one coefficient ``s > 1``, once pushed through the
    stages that read it, the array is split into its phases along that
    axis and each read becomes a unit-stride read of one phase: a produced
    array gets one copy of its stage per phase read (``_phase_copy``), an
    input one plane per phase read, which ``run()`` slices.  Where strides
    mix, the axis stays whole and its reads strided (``_take``).  Returns
    the stages, each replaced by its copies in phase order, and the phase
    map: copy or plane -> (array, (row, col) strides, phases)."""
    pending: dict[str, list[_Access]] = {}  # array -> reads not yet split
    phases: dict[str, tuple] = {}

    def split(arr: str, accs: list[_Access]) -> tuple[tuple, dict]:
        stride = []
        for d in range(2):
            ks = {a.dims[d][1] for a in accs if a.dims[d][0] is not None}
            stride.append(ks.pop() if len(ks) == 1 else 1)
        stride = tuple(stride)
        names: dict[tuple, str] = {}
        for a in accs:
            u = tuple(c % st for (_, _, c), st in zip(a.dims, stride))
            a.dims = [(iv, k // st, c // st)
                      for (iv, k, c), st in zip(a.dims, stride)]
            if u not in names:
                names[u] = (arr if stride == (1, 1)
                            else f"{arr}@{u[0]}_{u[1]}")
                if stride != (1, 1):
                    phases[names[u]] = (arr, stride, u)
            a.array = names[u]
        return stride, names

    copies: list[list[_StagePlan]] = []
    for s in reversed(stages):
        # the unique sink is the last stage, and nothing reads it
        stride, names = (split(s.out, pending.pop(s.out)) if copies
                         else ((1, 1), {(0, 0): s.out}))
        cs = [_phase_copy(s, names[u], stride, u) for u in sorted(names)]
        for c in cs:
            for _, acc in c.nest.loads:
                pending.setdefault(acc.array, []).append(acc)
        copies.append(cs)
    for arr, accs in pending.items():  # the inputs
        split(arr, accs)
    return [c for cs in reversed(copies) for c in cs], phases


# ---------------------------------------------------------------------------
# Source emission helpers
# ---------------------------------------------------------------------------


def _lit(v: float) -> str:
    return repr(float(v))


def _affine_t(coef: int, const: int) -> str:
    if coef == 0:
        return str(const)
    if const == 0:
        return f"{coef} * t"
    return f"{coef} * t + {const}"


def _sel(const: int, coef: int, n: int):
    """Index item for the ``n`` elements ``coef * i + const``: a unit-stride
    slice, or a ``(start, n, step)`` triple that :func:`_index` decimates."""
    return f"{const}:{const + n}" if coef == 1 else (const, n, coef)


def _index(v: str, items: list) -> str:
    """Source for ``v[items]``.  A str item indexes as written (one without
    a colon drops its axis); a ``(start, n, step)`` item is sliced whole and
    decimated by the emitted ``_take``.  Mode B's strided reads come here,
    and Mode A's only on an axis whose strides mix (``_split_phases`` makes
    the others unit-stride reads of phases).  Each ``_take`` along axis 1,
    the lane axis, adds one to the counter ``hls.lane_takes``."""
    expr = f"{v}[" + ", ".join(":" if isinstance(it, tuple) else it
                               for it in items) + "]"
    axis = 0
    for it in items:
        if isinstance(it, tuple):
            expr = f"_take({expr}, {axis}, {it[0]}, {it[1]}, {it[2]})"
            if axis == 1:
                telemetry.count("hls.lane_takes")
        if isinstance(it, tuple) or ":" in it:
            axis += 1
    return expr


# A strided slice of a value has no lowering on the chip (Mosaic lowers
# neither strided vector slices nor, for a last dim other than 128, strided
# loads), so the generated module takes a run of ``n * step`` rows, splits
# it into (n, step) with a reshape and keeps one phase; columns go through a
# transpose.  Both are exact data movement, and the column take moves every
# vreg through a transpose twice.  Mode A reaches it only where an axis's
# strides mix; elsewhere its strided reads are phase planes that run()
# slices with XLA before the kernel (``_split_phases``).
_TAKE_HELPER = '''

def _take(x, axis, start, n, step):
    """x[start : start + step * (n - 1) + 1 : step] along axis 0 or 1."""
    if axis == 1:
        return _take(x.T, 0, start, n, step).T
    phase = min(step - 1, start)
    over = start - phase + n * step - x.shape[0]
    if over > 0:
        x = jnp.concatenate([x, jnp.zeros((over,) + x.shape[1:], x.dtype)])
    lo = start - phase
    return x[lo:lo + n * step].reshape((n, step) + x.shape[1:])[:, phase]
'''


def _emit_streamed(p: Program, plan: _StreamPlan, buffering: str,
                   dtype: str) -> tuple[str, dict]:
    B, T = plan.block_rows, plan.grid
    sink = plan.sink
    cout = p.arrays[sink.out].shape[1]
    rout = sink.nest.trips[0]
    produced = {s.out: s for s in plan.stages}
    refs = [f"r_{_ident(a)}" for a in plan.inputs]

    # One window load per (input, row rate), starting on a sublane-tile
    # boundary; every tap is a static slice of it.  The chip's compiler
    # refuses a dynamic row offset it cannot prove to be tile-aligned (a
    # one-step grid has t = 0, so its offsets are static).
    spans: dict[tuple, list[int]] = {}  # (array, rate) -> [lo, hi) rows
    for s in plan.stages:
        for _, acc in s.nest.loads:
            if acc.array in produced:
                continue
            rc, rk = acc.dims[0][1], acc.dims[0][2]
            lo = rc * s.win_b + rk
            hi = lo + rc * (s.win_sz - 1) + 1
            w = spans.setdefault((acc.array, rc * s.win_a), [lo, hi])
            w[0], w[1] = min(w[0], lo), max(w[1], hi)
    body: list[str] = []
    windows: dict[tuple, tuple[str, int]] = {}  # (array, rate) -> (var, lo)
    for (arr, a), (lo, hi) in spans.items():
        lo -= lo % SUBLANE_ROWS
        start = _affine_t(a if T > 1 else 0, lo)
        if T > 1 and a % SUBLANE_ROWS == 0:
            start = f"pl.multiple_of({start}, {SUBLANE_ROWS})"
        ld = f"ld_{_ident(arr)}{len(windows)}"
        body.append(f"{ld} = r_{_ident(arr)}[pl.ds({start}, {hi - lo}), :]")
        windows[(arr, a)] = ld, lo
    final = None
    for s in plan.stages:
        tag = s.nest.loop.ivname
        csz = s.nest.trips[1]
        body.append(f"# stage {tag}: '{s.out}' domain rows "
                    f"[{s.win_a}*t+{s.win_b}, +{s.win_sz})")
        names: dict[str, str] = {}
        for op in s.nest.ops:
            if isinstance(op, ConstOp):
                names[op.result] = _lit(op.value)
            elif isinstance(op, LoadOp):
                acc = next(a for o, a in s.nest.loads if o is op)
                (_, rc, rk), (civ, cc, ck) = acc.dims
                if acc.array in produced:
                    prod = produced[acc.array]
                    rel = rc * s.win_b + rk - prod.r0 - prod.win_b
                    c0 = ck - prod.c0
                    src = f"w_{_ident(acc.array)}"
                else:
                    src, lo = windows[(acc.array, rc * s.win_a)]
                    rel = rc * s.win_b + rk - lo
                    c0 = ck
                csel = (f"{c0}:{c0 + 1}" if civ is None
                        else _sel(c0, cc, csz))
                expr = _index(src, [_sel(rel, rc, s.win_sz), csel])
                names[op.result] = _vname(op.result)
                body.append(f"{names[op.result]} = {expr}")
            elif isinstance(op, ArithOp):
                names[op.result] = _vname(op.result)
                body.append(f"{names[op.result]} = " + _ARITH_FMT[op.fn]
                            .format(*(names[a] for a in op.args)))
            elif isinstance(op, StoreOp):
                val = names[op.value]
                if s is sink:
                    final = val
                else:
                    body.append(f"w_{_ident(s.out)} = jnp.broadcast_to("
                                f"{val}, ({s.win_sz}, {csz}))")
    assert final is not None
    store_val = f"jnp.broadcast_to({final}, ({B}, {cout}))"

    lines = [
        '"""Generated by repro.core.codegen — do not edit."""',
        "import jax",
        "import jax.numpy as jnp",
        "from jax.experimental import pallas as pl",
        "",
        f"DTYPE = jnp.dtype('{dtype}')",
    ]
    if any("_take(" in b for b in body):
        lines.append(_TAKE_HELPER.rstrip("\n"))
    lines += [
        "",
        "",
        "def _kernel(" + ", ".join(refs + ["o_ref"]) + "):",
    ]
    if buffering == "double":
        lines.append("    t = pl.program_id(0)")
        lines += ["    " + b for b in body]
        lines.append(f"    o_ref[...] = {store_val}")
    else:
        lines.append("    def _tile(t, carry):")
        lines += ["        " + b for b in body]
        lines.append(f"        o_ref[pl.ds({B} * t, {B}), :] = {store_val}")
        lines.append("        return carry")
        lines.append(f"    jax.lax.fori_loop(0, {T}, _tile, 0)")
    lines += ["", "",
              "def run(arrays, interpret=False):"]
    args = []
    specs = []
    pad_elems = 0
    # each ref: (array, strides, phases), the whole array where unsplit
    refs_of = {a: plan.phases.get(a, (a, (1, 1), (0, 0)))
               for a in plan.inputs}
    for a in dict.fromkeys(src for src, _, _ in refs_of.values()):
        v = f"x_{_ident(a)}"
        lines.append(f"    {v} = jnp.asarray(arrays['{a}'], DTYPE)")
        pad = plan.pad_rows.get(a, 0)
        h, w = p.arrays[a].shape
        if pad:
            lines.append(f"    {v} = jnp.pad({v}, ((0, {pad}), (0, 0)), "
                         "mode='edge')")
            pad_elems += (h + pad) * w
        # Phase planes are sliced after the edge pad, columns first, as
        # rows of the transpose: on the chip a step along rows runs at
        # copy speed, one along the lane axis element by element (a
        # stepped jnp index is a gather; a strided lax.slice is faster,
        # but still ten times slower than the transposes).
        cols = sorted({(st[1], u[1]) for src, st, u in refs_of.values()
                       if src == a and st[1] > 1})
        if cols:
            lines.append(f"    t_{_ident(a)} = {v}.T")
        for sc, q in cols:
            lines.append(f"    c{q}_{_ident(a)} = t_{_ident(a)}[{q}::{sc}].T")
    for a, (src, stride, phase) in refs_of.items():
        v = f"x_{_ident(a)}"
        if a in plan.phases:
            (sr, sc), (pr, pc) = stride, phase
            x = f"c{pc}_{_ident(src)}" if sc > 1 else f"x_{_ident(src)}"
            lines.append(f"    {v} = {x}" + (f"[{pr}::{sr}]" if sr > 1 else ""))
        rows = p.arrays[src].shape[0] + plan.pad_rows.get(src, 0)
        h, w = (-(-(n - u) // st) for n, st, u in
                zip((rows, p.arrays[src].shape[1]), stride, phase))
        args.append(v)
        specs.append(f"pl.BlockSpec(({h}, {w}), lambda t: (0, 0))")
    lines.append("    out = pl.pallas_call(")
    lines.append("        _kernel,")
    if buffering == "double":
        lines.append(f"        grid=({T},),")
        lines.append("        in_specs=[" + ", ".join(specs) + "],")
        lines.append(f"        out_specs=pl.BlockSpec(({B}, {cout}), "
                     "lambda t: (t, 0)),")
    lines.append(f"        out_shape=jax.ShapeDtypeStruct(({T * B}, {cout}), "
                 "DTYPE),")
    lines.append("        interpret=interpret,")
    lines.append("    )(" + ", ".join(args) + ")")
    trim = f"[:{rout}]" if T * B != rout else ""
    lines.append(f"    return {{'{sink.out}': out{trim}}}")
    window: dict[str, int] = {}  # produced array -> elements, all copies
    for s in plan.stages[:-1]:
        arr = plan.phases.get(s.out, (s.out,))[0]
        window[arr] = window.get(arr, 0) + s.win_sz * s.nest.trips[1]
    meta = {"mode": "streamed", "grid": (T,), "block_rows": B,
            "halo": dict(plan.halo), "outputs": (sink.out,),
            "vmem_window_elems": window, "edge_pad_elems": pad_elems,
            "phase_planes": sum(a in plan.phases for a in plan.inputs)}
    return "\n".join(lines) + "\n", meta


# Strided stores can't use `.at[::step].set` inside a Pallas kernel (the
# scatter lowering captures index constants, which pallas_call rejects), so
# the generated module spreads the value and selects the strided positions
# with an iota mask.  The spread is a broadcast and a row-merging reshape
# (columns via a transpose): the chip lowers no lane-splitting reshape,
# which is what jnp.repeat along columns becomes.
_STRIDED_SET_HELPER = '''

def _spread(x, axis, step):
    """jnp.repeat(x, step, axis) for axis 0 or 1."""
    if axis == 1:
        return _spread(x.T, 0, step).T
    wide = jnp.broadcast_to(x[:, None], (x.shape[0], step) + x.shape[1:])
    return wide.reshape((x.shape[0] * step,) + x.shape[1:])


def _strided_set(dst, val, starts, steps):
    sp = val
    for ax, st in enumerate(steps):
        if st > 1:
            sp = _spread(sp, ax, st)
    sp = sp[tuple(slice(0, dst.shape[a] - starts[a]) for a in range(sp.ndim))]
    sp = jnp.pad(sp, tuple(
        (starts[a], dst.shape[a] - starts[a] - sp.shape[a])
        for a in range(sp.ndim)))
    mask = None
    for ax, (s0, st, n) in enumerate(zip(starts, steps, val.shape)):
        i = jax.lax.broadcasted_iota(jnp.int32, dst.shape, ax)
        m = (i >= s0) & (i < s0 + st * (n - 1) + 1)
        if st > 1:
            m = m & ((i - s0) % st == 0)
        mask = m if mask is None else (mask & m)
    return jnp.where(mask, sp, dst)
'''


def _align_suffix(val_axes: list, outs: list) -> str:
    """Indexing suffix aligning a loaded value's axes with the accumulator's
    (store-dim-ordered) axes; empty when broadcasting already lines up."""
    if len(outs) <= 1 or val_axes == outs:
        return ""
    if len(val_axes) == 2:
        return ".T"
    if not val_axes:
        return ""  # scalar broadcasts
    return "[:, None]" if val_axes[0] == outs[0] else "[None, :]"


def _emit_whole(p: Program, nests: list[_Nest], dtype: str) -> tuple[str, dict]:
    stored = []
    for nest in nests:
        for _, acc in nest.stores:
            if acc.array not in stored:
                stored.append(acc.array)
    order = list(p.arrays)
    refs = [f"r_{_ident(a)}" for a in order]
    outs = [f"o_{_ident(a)}" for a in stored]

    body: list[str] = []
    inited: set[str] = set()

    def init(a: str):
        if a not in inited:
            body.append(f"v_{_ident(a)} = r_{_ident(a)}[...]")
            inited.add(a)

    red_count = 0
    for nest in nests:
        ivpos = {ivn: k for k, ivn in enumerate(nest.ivs)}
        trips = nest.trips
        if nest.red_iv is not None:
            # canonical accumulation: vectorize the outer ivs, fold the
            # innermost one — a left fold in program order, so the float
            # rounding matches sequential_exec bit for bit (the _exact
            # golden tests rely on this).  The fold is a Python loop, so
            # every step indexes its operands statically: the chip lowers
            # no dynamic_slice of a value, which a lax.fori_loop fold
            # indexing by its counter becomes.
            _, sacc = nest.stores[0]
            init(sacc.array)
            red_outs = [ivn for ivn, _, _ in sacc.dims]
            nk = trips[-1]
            acc_shape = tuple(trips[ivpos[ivn]] for ivn, _, _ in sacc.dims)
            sels = [_sel(const, coef, trips[ivpos[ivn]])
                    for ivn, coef, const in sacc.dims]
            dst = f"v_{_ident(sacc.array)}"
            body.append(f"# nest {nest.loop.ivname}: reduction over "
                        f"'{nest.red_iv}' ({nk} steps), domain {acc_shape}")
            acc0 = f"a_red{red_count}"
            body.append(f"{acc0} = {_index(dst, sels)}")
            inner: list[str] = []
            names = {}
            final = None
            for op in nest.ops:
                if isinstance(op, ConstOp):
                    names[op.result] = _lit(op.value)
                elif isinstance(op, LoadOp):
                    if op.uid in nest.red_loads:
                        names[op.result] = "_acc"  # the fold carry
                        continue
                    acc_ = next(a for o, a in nest.loads if o is op)
                    init(acc_.array)
                    lsels, val_axes = [], []
                    for ivn, coef, const in acc_.dims:
                        if ivn is None:
                            lsels.append(str(const))
                        elif ivn == nest.red_iv:
                            ix = "_k" if coef == 1 else f"{coef} * _k"
                            lsels.append(f"{ix} + {const}" if const else ix)
                        else:
                            n = trips[ivpos[ivn]]
                            lsels.append(_sel(const, coef, n))
                            val_axes.append(ivn)
                    expr = (_index(f"v_{_ident(acc_.array)}", lsels)
                            + _align_suffix(val_axes, red_outs))
                    names[op.result] = _vname(op.result)
                    inner.append(f"{names[op.result]} = {expr}")
                elif isinstance(op, ArithOp):
                    names[op.result] = _vname(op.result)
                    inner.append(f"{names[op.result]} = " + _ARITH_FMT[op.fn]
                                 .format(*(names[a] for a in op.args)))
                elif isinstance(op, StoreOp):
                    final = names[op.value]
            assert final is not None
            body.append(f"def _red{red_count}(_k, _acc):")
            body += ["    " + b for b in inner]
            body.append(f"    return jnp.broadcast_to({final}, "
                        f"{acc_shape!r}).astype(DTYPE)")
            body.append(f"for _k in range({nk}):")
            body.append(f"    {acc0} = _red{red_count}(_k, {acc0})")
            starts = [const for _, _, const in sacc.dims]
            steps = [coef for _, coef, _ in sacc.dims]
            shape = p.arrays[sacc.array].shape
            full = (all(st == 1 for st in steps)
                    and all(s0 == 0 for s0 in starts)
                    and acc_shape == shape)
            if full:
                body.append(f"{dst} = jnp.broadcast_to({acc0}, {shape!r})")
            elif all(st == 1 for st in steps):
                body.append(f"{dst} = {dst}.at[" + ", ".join(sels)
                            + f"].set({acc0})")
            else:
                exts_t = ("(" + ", ".join(map(str, acc_shape))
                          + ("," if len(acc_shape) == 1 else "") + ")")
                body.append(
                    f"{dst} = _strided_set({dst}, jnp.broadcast_to({acc0}, "
                    f"{exts_t}), {tuple(starts)!r}, {tuple(steps)!r})")
            red_count += 1
            continue
        body.append(f"# nest {nest.loop.ivname}: domain {tuple(trips)}")
        names: dict[str, str] = {}
        for op in nest.ops:
            if isinstance(op, ConstOp):
                names[op.result] = _lit(op.value)
            elif isinstance(op, LoadOp):
                acc = next(a for o, a in nest.loads if o is op)
                init(acc.array)
                sels, axis_ivs = [], []
                for ivn, coef, const in acc.dims:
                    if ivn is None:
                        sels.append(f"{const}:{const + 1}")
                        axis_ivs.append(None)
                    else:
                        n = trips[ivpos[ivn]]
                        sels.append(_sel(const, coef, n))
                        axis_ivs.append(ivn)
                v = f"v_{_ident(acc.array)}"
                if len(acc.dims) == 1:
                    (ivn, coef, const), = acc.dims
                    if ivn is None:
                        expr = f"{v}[{const}]"
                    else:
                        expr = _index(v, sels)
                        if len(nest.ivs) == 2 and ivpos[ivn] == 0:
                            expr += "[:, None]"
                elif all(x is None for x in axis_ivs):
                    expr = f"{v}[{acc.dims[0][2]}, {acc.dims[1][2]}]"
                elif len(nest.ivs) == 1:
                    # depth-1 nest reading a 2-D array: collapse the
                    # constant axis so the value is 1-D over the nest iv
                    if axis_ivs[0] is None:
                        expr = _index(v, [str(acc.dims[0][2]), sels[1]])
                    else:
                        expr = _index(v, [sels[0], str(acc.dims[1][2])])
                else:
                    expr = _index(v, sels)
                    # align value axes with the (outer, inner) target:
                    # transpose when an axis varies over the wrong iv
                    if any(ivn is not None and ivpos[ivn] != k
                           for k, ivn in enumerate(axis_ivs)):
                        expr += ".T"
                names[op.result] = _vname(op.result)
                body.append(f"{names[op.result]} = {expr}")
            elif isinstance(op, ArithOp):
                names[op.result] = _vname(op.result)
                body.append(f"{names[op.result]} = " + _ARITH_FMT[op.fn]
                            .format(*(names[a] for a in op.args)))
            elif isinstance(op, StoreOp):
                acc = next(a for o, a in nest.stores if o is op)
                init(acc.array)
                sels, starts, steps, exts = [], [], [], []
                for ivn, coef, const in acc.dims:
                    n = trips[ivpos[ivn]]
                    sels.append(_sel(const, coef, n))
                    starts.append(const)
                    steps.append(coef)
                    exts.append(n)
                val = names[op.value]
                if (len(acc.dims) == 2
                        and ivpos[acc.dims[0][0]] == 1):  # transposed store
                    # exts are already in destination-dim order, matching
                    # the transposed value
                    val = f"jnp.asarray({val}).T"
                v = f"v_{_ident(acc.array)}"
                shape = p.arrays[acc.array].shape
                full = (all(st == 1 for st in steps)
                        and all(s0 == 0 for s0 in starts)
                        and tuple(exts) == shape)
                if full:
                    # a full-array `.at[...].set` hits a scatter path whose
                    # lowering captures constants (rejected by pallas_call);
                    # a full store is just a broadcast reassignment
                    body.append(f"{v} = jnp.broadcast_to({val}, {shape!r})")
                elif all(st == 1 for st in steps):
                    body.append(f"{v} = {v}.at[" + ", ".join(sels) +
                                f"].set({val})")
                else:
                    exts_t = ("(" + ", ".join(map(str, exts))
                              + ("," if len(exts) == 1 else "") + ")")
                    body.append(
                        f"{v} = _strided_set({v}, jnp.broadcast_to({val}, "
                        f"{exts_t}), {tuple(starts)!r}, {tuple(steps)!r})")
    for a in stored:
        init(a)  # a store-only nest filtered out earlier can't happen,
        body.append(f"o_{_ident(a)}[...] = v_{_ident(a)}")

    lines = [
        '"""Generated by repro.core.codegen — do not edit."""',
        "import jax",
        "import jax.numpy as jnp",
        "from jax.experimental import pallas as pl",
        "",
        f"DTYPE = jnp.dtype('{dtype}')",
    ]
    if any("_take(" in b for b in body):
        lines.append(_TAKE_HELPER.rstrip("\n"))
    if any("_strided_set(" in b for b in body):
        lines.append(_STRIDED_SET_HELPER.rstrip("\n"))
    lines += [
        "",
        "",
        "def _kernel(" + ", ".join(refs + outs) + "):",
    ]
    lines += ["    " + b for b in body]
    lines += ["", "",
              "def run(arrays, interpret=False):"]
    for a in order:
        lines.append(f"    x_{_ident(a)} = jnp.asarray(arrays['{a}'], DTYPE)")
    shapes = ", ".join(
        f"jax.ShapeDtypeStruct({p.arrays[a].shape!r}, DTYPE)" for a in stored)
    lines.append("    outs = pl.pallas_call(")
    lines.append("        _kernel,")
    lines.append(f"        out_shape=[{shapes}],")
    lines.append("        interpret=interpret,")
    lines.append("    )(" + ", ".join(f"x_{_ident(a)}" for a in order) + ")")
    lines.append("    return {" + ", ".join(
        f"'{a}': outs[{i}]" for i, a in enumerate(stored)) + "}")
    meta = {"mode": "whole", "grid": (), "block_rows": None, "halo": {},
            "outputs": tuple(stored), "vmem_window_elems": {},
            "edge_pad_elems": 0, "phase_planes": 0}
    return "\n".join(lines) + "\n", meta


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@dataclass
class PallasKernel:
    """An executable lowering of a Program.

    ``fn(arrays, interpret=False) -> dict`` maps input arrays (by name, the
    same dict ``sim.make_inputs`` produces) to the produced output arrays.
    ``source`` is the emitted kernel module text — the debuggable artifact.
    """

    program_name: str
    mode: str                       # "streamed" | "whole"
    buffering: str                  # "double" | "single"
    source: str
    fn: Callable
    outputs: tuple
    grid: tuple
    block_rows: Optional[int]
    halo: dict = field(default_factory=dict)
    vmem_window_elems: dict = field(default_factory=dict)
    soft_reasons: list = field(default_factory=list)
    modeled_latency: Optional[int] = None
    point_desc: Optional[str] = None
    fusion_shifts: list = field(default_factory=list)

    def __call__(self, arrays, interpret=False):
        return self.fn(arrays, interpret=interpret)


def lower_program(p: Program, *, block_rows: Optional[int] = None,
                  buffering: str = "double",
                  dtype: str = "float32") -> PallasKernel:
    """Lower ``p`` to a Pallas kernel (streamed if the chain contract holds,
    whole-array otherwise); raises :class:`UnlowerableProgram` when the
    program is outside both contracts."""
    if buffering not in ("double", "single"):
        raise ValueError("buffering must be 'double' or 'single', "
                         f"got {buffering!r}")
    with telemetry.span("hls.lower"):
        # A program whose affine accesses can leave their arrays has no
        # faithful kernel — jnp indexing clamps silently, hiding the bug.
        # The linter proves the bounds (or the violation) statically; other
        # lint findings stay warnings, but OOB is a hard refusal here.
        from .analysis import lint as _lint
        oob = [d for d in _lint(p) if d.code in ("oob-read", "oob-write")]
        if oob:
            raise UnlowerableProgram(p.name, [
                NestContractViolation(d.code, "codegen",
                                      f"{d.where}: {d.detail}") for d in oob])
        with telemetry.span("hls.lower.extract"):
            nests, hard = _extract_nests(p)
        if hard:
            raise UnlowerableProgram(p.name, hard)
        if not nests:
            raise UnlowerableProgram(p.name, [NestContractViolation(
                "empty", "codegen", "program has no loop nests")])
        with telemetry.span("hls.lower.plan"):
            plan, soft = _plan_streamed(p, nests, block_rows or SUBLANE_ROWS)
        with telemetry.span("hls.lower.emit"):
            if plan is not None:
                src, meta = _emit_streamed(p, plan, buffering, dtype)
            else:
                src, meta = _emit_whole(p, nests, dtype)
        ns: dict = {}
        with telemetry.span("hls.lower.exec"):
            exec(compile(src, f"<codegen:{p.name}>", "exec"), ns)
        # what the emitted run() edge-pads, every input whole, per call,
        # and the phase planes it then slices
        telemetry.count("hls.edge_pad_bytes",
                        meta["edge_pad_elems"] * ns["DTYPE"].itemsize)
        telemetry.count("hls.phase_planes", meta["phase_planes"])
        telemetry.count("hls.lowers")
    return PallasKernel(program_name=p.name, mode=meta["mode"],
                        buffering=buffering if meta["mode"] == "streamed"
                        else "whole", source=src, fn=ns["run"],
                        outputs=meta["outputs"], grid=meta["grid"],
                        block_rows=meta["block_rows"], halo=meta["halo"],
                        vmem_window_elems=meta["vmem_window_elems"],
                        soft_reasons=soft)


def _point_block_rows(point) -> Optional[int]:
    """block_rows from a design point: the tile pass marks the outer strip
    loop with ``tile_block``; fall back to the LoopTile pass config.  The
    tile is rounded up to a multiple of ``SUBLANE_ROWS`` so the row block is
    one the chip accepts; ``_plan_streamed`` clamps it to the output rows,
    so an output shorter than that becomes one whole-array block."""
    blocks = [l.tile_block for l in point.program.loops()
              if getattr(l, "tile_block", None)]
    if not blocks:
        from .transforms import LoopTile
        for ps in point.passes:
            if isinstance(ps, LoopTile):
                blocks.extend(ps.seq if ps.seq is not None
                              else ps.sizes.values())
    if not blocks:
        return None
    return -(-max(blocks) // SUBLANE_ROWS) * SUBLANE_ROWS


def emit_pallas(result, point=None, *, buffering: str = "double",
                block_rows: Optional[int] = None,
                dtype: str = "float32") -> PallasKernel:
    """Lower a ``CompileResult`` design point (default: ``result.best``) to
    an executable Pallas kernel.  The tile pass supplies ``block_rows``, the
    fusion log rides along as ``kernel.fusion_shifts`` (the streamed
    window's ``halo`` generalizes the fusion row shift).  Unlowerable
    programs raise :class:`UnlowerableProgram` *and* record a
    ``codegen-unlowerable`` diagnostic on the result."""
    point = point if point is not None else result.best
    if block_rows is None:
        block_rows = _point_block_rows(point)
    try:
        k = lower_program(result.program, block_rows=block_rows,
                          buffering=buffering, dtype=dtype)
    except UnlowerableProgram as e:
        result.diagnostics.append({
            "kind": "codegen-unlowerable", "program": e.program_name,
            "reasons": list(e.reasons),
            "codes": [v.code for v in e.violations]})
        raise
    k.modeled_latency = point.latency
    k.point_desc = point.desc
    k.fusion_shifts = [dict(x) for x in
                       getattr(point.program, "_fusion_log", [])]
    return k
