"""JAX tracing frontend: build Program IR from real kernels (DESIGN.md §11).

``trace(fn, *example_args)`` runs ``jax.make_jaxpr`` on a shape-specialized
JAX function and interprets the jaxpr into the affine dialect: every tensor
equation becomes one perfect loop nest storing a fresh intermediate array,
pure layout primitives (broadcast/transpose/squeeze/slice/1-reshape) become
*views* — affine re-indexings that never materialize — and ``lax.scan``
becomes a recurrence loop whose carry lives in a time-indexed state array
(``C[t+1] = f(C[t], xs[t])``), so a traced scan is exactly the multi-loop
task shape ``ir.nest_shape`` reports as ``multi_loop`` and the generalized
dependence model understands.

Reductions (``reduce_sum``/``reduce_max``, ``dot_general`` contractions) are
unrolled into left-fold op chains — the same element order ``sequential_exec``
and the XLA CPU loops use — which keeps the differential check tight:
``TracedProgram.validate()`` runs the traced Program through
``sim.sequential_exec`` and the original function under ``enable_x64`` on the
same inputs and compares at ``rtol=1e-12``.

The frontend is deliberately narrow: the supported primitive set is the one
the bundled kernels need (wkv6 recurrence, separable conv block, softmax
attention).  Anything else raises the structured
:class:`errors.UntraceableFunction` naming the offending primitive, so
callers widen the kernel instead of string-matching a trace dump.

Entry points ``wkv6_program`` / ``conv_block_program`` /
``attention_program`` trace single-head, tiny-shape variants of the real
kernels in ``repro.kernels`` (same math, scalar loop form) — small enough
for the DSE yet structurally faithful: the wkv6 trace carries the
data-dependent-decay recurrence, the attention trace the two matmuls and
the max/sum softmax reductions.
"""
from __future__ import annotations

import inspect
import itertools
import math
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import numpy as np

from .errors import UntraceableFunction
from .ir import AffExpr, Program, ProgramBuilder, aff

import jax
import jax.numpy as jnp
from jax.extend.core import Literal as _Literal

#: widest reduction/contraction the tracer will unroll into an op chain.
MAX_UNROLL = 256

_ELT2 = {"add": "add", "sub": "sub", "mul": "mul", "div": "div",
         "max": "max", "min": "min"}
_ELT1 = {"exp": "exp"}
_PYFOLD = {"add": lambda a, b: a + b, "sub": lambda a, b: a - b,
           "mul": lambda a, b: a * b, "div": lambda a, b: a / b,
           "max": max, "min": min, "exp": math.exp, "neg": lambda a: -a}

# storage preset for every traced array (same dual-read BRAM banking the
# hand-built benchmarks use, so DSE moves see familiar resource tradeoffs)
_STORAGE = dict(kind="bram", ports=("w", "r", "r", "r"), partition=(0,))


@dataclass(frozen=True)
class _Val:
    """A traced tensor value: an IR array plus an affine view.

    ``spec`` has one entry per ARRAY dim: either an :class:`AffExpr`
    (context-fixed index — a scan iv, a constant) or ``(dim, coef, const)``
    mapping the value's logical ``dim`` onto the array dim as
    ``coef * iv + const``.  Layout primitives only rewrite ``spec``."""

    array: str
    shape: tuple  # logical shape (may be () for scalars)
    spec: tuple


class _Tracer:
    def __init__(self, name: str):
        self.b = ProgramBuilder(name)
        self._ivn = itertools.count()
        self._arrn = itertools.count()
        # open scan context: list of (time AffExpr, extent)
        self.prefix: list = []
        self.fn_name = name

    # -- plumbing -----------------------------------------------------------
    def _die(self, primitive: str, detail: str = ""):
        raise UntraceableFunction(self.fn_name, primitive, detail)

    def _iv(self, stem: str = "i") -> str:
        return f"{stem}{next(self._ivn)}"

    def _new_array(self, full_shape, is_arg=False, name=None) -> str:
        name = name if name is not None else f"t{next(self._arrn)}"
        self.b.array(name, tuple(int(x) for x in full_shape),
                     is_arg=is_arg, **_STORAGE)
        return name

    def _spec(self, lead: tuple, shape: tuple) -> tuple:
        ents = list(lead)
        ents += [(d, 1, 0) for d in range(len(shape))] if shape else [aff(0)]
        return tuple(ents)

    def _load(self, val: _Val, els: Sequence[AffExpr]) -> str:
        idx = []
        for ent in val.spec:
            if isinstance(ent, AffExpr):
                idx.append(ent)
            else:
                d, coef, const = ent
                idx.append(els[d] * coef + const)
        return self.b.load(val.array, *idx)

    def _bload(self, v, els, out_shape) -> str:
        """Load ``v`` at the nest point ``els``, numpy-broadcasting
        size-1 value dims (and scalars) against ``out_shape``."""
        if isinstance(v, float):
            return self.b.const(v)
        if not v.shape:
            return self._load(v, [])
        if len(v.shape) != len(out_shape):
            self._die("broadcast", f"rank {len(v.shape)} operand against "
                                   f"rank {len(out_shape)} result")
        adj = [aff(0) if v.shape[k] == 1 and out_shape[k] != 1 else els[k]
               for k in range(len(v.shape))]
        return self._load(v, adj)

    def _emit_nest(self, shape, body_fn, *, store_arr=None,
                   store_lead=(), pre_drop=0) -> _Val:
        """One perfect nest over ``shape`` inside the open scan prefix;
        ``body_fn(els) -> ssa`` computes the element, which is stored into
        ``store_arr`` (fresh intermediate when None).  ``pre_drop`` drops
        that many innermost prefix dims from the store index — used when
        ``store_lead`` itself supplies the time index (carry store-back)."""
        shape = tuple(int(s) for s in shape)
        loop_shape = shape or (1,)
        pre = tuple(e for e, _ in self.prefix)
        if pre_drop:
            pre = pre[:len(pre) - pre_drop]
        if store_arr is None:
            full = tuple(n for _, n in self.prefix) + loop_shape
            store_arr = self._new_array(full)
        ctxs, ivs = [], []
        for n in loop_shape:
            ctx = self.b.loop(self._iv(), 0, n)
            ivs.append(ctx.__enter__())
            ctxs.append(ctx)
        val = body_fn(ivs if shape else [])
        self.b.store(store_arr, val, *(pre + tuple(store_lead) + tuple(ivs)))
        for ctx in reversed(ctxs):
            ctx.__exit__()
        return _Val(store_arr, shape, self._spec(pre + tuple(store_lead),
                                                 shape))

    # -- jaxpr interpretation ----------------------------------------------
    def _lift_const(self, c):
        a = np.asarray(c)
        if a.size == 1:
            return float(a.reshape(()))
        self._die("constant", f"array constant of shape {a.shape} "
                              "(pass it as a function argument)")

    def _read(self, atom, env):
        if isinstance(atom, _Literal):
            return self._lift_const(atom.val)
        return env[atom]

    def run(self, closed, invals):
        jx = closed.jaxpr
        env = {}
        for var, c in zip(jx.constvars, closed.consts):
            env[var] = self._lift_const(c)
        for var, v in zip(jx.invars, invals):
            env[var] = v
        for eqn in jx.eqns:
            self._eqn(eqn, env)
        return [self._read(a, env) for a in jx.outvars]

    def _eqn(self, eqn, env):
        prim = eqn.primitive.name
        invals = [self._read(a, env) for a in eqn.invars]
        params = eqn.params
        if prim == "scan":
            outs = self._scan(eqn, invals)
            for var, v in zip(eqn.outvars, outs):
                if type(var).__name__ != "DropVar":
                    env[var] = v
            return
        if prim in ("pjit", "closed_call", "core_call", "custom_jvp_call",
                    "custom_vjp_call", "remat2", "checkpoint"):
            inner = params.get("jaxpr") or params.get("call_jaxpr")
            if inner is None:
                self._die(prim, "call primitive without an inner jaxpr")
            outs = self.run(inner, invals)
            for var, v in zip(eqn.outvars, outs):
                if type(var).__name__ != "DropVar":
                    env[var] = v
            return
        out_var = eqn.outvars[0]
        out_shape = tuple(out_var.aval.shape)
        if prim in _ELT2 or prim in _ELT1 or prim in ("neg", "integer_pow"):
            if all(isinstance(v, float) for v in invals) and prim in _PYFOLD:
                env[out_var] = _PYFOLD[prim](*invals)
                return
            env[out_var] = self._elementwise(prim, params, invals, out_shape)
        elif prim == "broadcast_in_dim":
            env[out_var] = self._broadcast(invals[0], params, out_shape)
        elif prim == "transpose":
            env[out_var] = self._transpose(invals[0], params["permutation"],
                                           out_shape)
        elif prim == "squeeze":
            env[out_var] = self._squeeze(invals[0], params["dimensions"],
                                         out_shape)
        elif prim == "reshape":
            env[out_var] = self._reshape(invals[0], out_shape)
        elif prim == "slice":
            env[out_var] = self._slice(invals[0], params, out_shape)
        elif prim in ("reduce_sum", "reduce_max", "reduce_min"):
            env[out_var] = self._reduce(prim, invals[0], params["axes"],
                                        out_shape)
        elif prim == "dot_general":
            env[out_var] = self._dot(invals[0], invals[1],
                                     params["dimension_numbers"], out_shape)
        elif prim in ("convert_element_type", "stop_gradient", "copy"):
            env[out_var] = invals[0]
        else:
            self._die(prim)

    # -- compute primitives -------------------------------------------------
    def _elementwise(self, prim, params, invals, out_shape) -> _Val:
        def body(els):
            if prim == "neg":
                z = self.b.const(0.0)
                return self.b.sub(z, self._bload(invals[0], els, out_shape))
            if prim == "integer_pow":
                y = int(params["y"])
                if y < 1:
                    self._die("integer_pow", f"exponent {y}")
                x = self._bload(invals[0], els, out_shape)
                acc = x
                for _ in range(y - 1):
                    acc = self.b.mul(acc, x)
                return acc
            args = [self._bload(v, els, out_shape) for v in invals]
            return self.b.arith(_ELT2.get(prim) or _ELT1[prim], *args)

        return self._emit_nest(out_shape, body)

    def _reduce(self, prim, v, axes, out_shape) -> _Val:
        if isinstance(v, float):
            self._die(prim, "reduction of a constant")
        axes = tuple(sorted(int(a) for a in axes))
        extents = [v.shape[a] for a in axes]
        count = 1
        for n in extents:
            count *= n
        if count > MAX_UNROLL:
            self._die(prim, f"reduction of {count} elements exceeds the "
                            f"unroll budget ({MAX_UNROLL})")
        fn = {"reduce_sum": "add", "reduce_max": "max",
              "reduce_min": "min"}[prim]

        def body(els):
            terms = []
            for combo in itertools.product(*[range(n) for n in extents]):
                full, free = [], iter(els)
                for k in range(len(v.shape)):
                    full.append(aff(combo[axes.index(k)]) if k in axes
                                else next(free))
                terms.append(self._load(v, full))
            acc = terms[0]  # left fold: sequential_exec's element order
            for t in terms[1:]:
                acc = self.b.arith(fn, acc, t)
            return acc

        return self._emit_nest(out_shape, body)

    def _dot(self, a, b, dimension_numbers, out_shape) -> _Val:
        (lc, rc), (lb, rb) = dimension_numbers
        if lb or rb or len(lc) != 1 or len(rc) != 1:
            self._die("dot_general", f"dimension_numbers {dimension_numbers}"
                                     " (batched/multi-axis contraction)")
        if isinstance(a, float) or isinstance(b, float):
            self._die("dot_general", "contraction with a constant operand")
        lc0, rc0 = int(lc[0]), int(rc[0])
        K = a.shape[lc0]
        if K > MAX_UNROLL:
            self._die("dot_general", f"contraction of {K} elements exceeds "
                                     f"the unroll budget ({MAX_UNROLL})")
        lf = [d for d in range(len(a.shape)) if d != lc0]
        rf = [d for d in range(len(b.shape)) if d != rc0]

        def body(els):
            acc = None
            for k in range(K):
                fa = [None] * len(a.shape)
                fa[lc0] = aff(k)
                for i, d in enumerate(lf):
                    fa[d] = els[i]
                fb = [None] * len(b.shape)
                fb[rc0] = aff(k)
                for j, d in enumerate(rf):
                    fb[d] = els[len(lf) + j]
                term = self.b.mul(self._load(a, fa), self._load(b, fb))
                acc = term if acc is None else self.b.add(acc, term)
            return acc

        return self._emit_nest(out_shape, body)

    # -- layout primitives (views: spec rewrites, no code) ------------------
    def _broadcast(self, v, params, out_shape):
        if isinstance(v, float):
            return v
        bd = tuple(int(d) for d in params["broadcast_dimensions"])
        ents = []
        for ent in v.spec:
            if isinstance(ent, AffExpr):
                ents.append(ent)
            else:
                d, coef, const = ent
                if v.shape[d] == 1 and out_shape[bd[d]] != 1:
                    ents.append(aff(const))  # stretched dim: index pins to 0
                else:
                    ents.append((bd[d], coef, const))
        return _Val(v.array, out_shape, tuple(ents))

    def _transpose(self, v, permutation, out_shape):
        if isinstance(v, float):
            return v
        perm = tuple(int(x) for x in permutation)
        inv = {d: j for j, d in enumerate(perm)}
        ents = [ent if isinstance(ent, AffExpr)
                else (inv[ent[0]], ent[1], ent[2]) for ent in v.spec]
        return _Val(v.array, out_shape, tuple(ents))

    def _squeeze(self, v, dimensions, out_shape):
        if isinstance(v, float):
            return v
        drop = set(int(d) for d in dimensions)
        remap = {}
        for d in range(len(v.shape)):
            if d not in drop:
                remap[d] = len(remap)
        ents = []
        for ent in v.spec:
            if isinstance(ent, AffExpr):
                ents.append(ent)
            elif ent[0] in drop:  # extent-1 dim: its iv is always 0
                ents.append(aff(ent[2]))
            else:
                ents.append((remap[ent[0]], ent[1], ent[2]))
        return _Val(v.array, out_shape, tuple(ents))

    def _reshape(self, v, out_shape):
        if isinstance(v, float):
            return v
        old_nz = [d for d in range(len(v.shape)) if v.shape[d] != 1]
        new_nz = [d for d in range(len(out_shape)) if out_shape[d] != 1]
        if [v.shape[d] for d in old_nz] != [out_shape[d] for d in new_nz]:
            self._die("reshape", f"{v.shape} -> {out_shape} (only inserting/"
                                 "removing size-1 dims is traceable)")
        remap = dict(zip(old_nz, new_nz))
        ents = []
        for ent in v.spec:
            if isinstance(ent, AffExpr):
                ents.append(ent)
            elif ent[0] in remap:
                ents.append((remap[ent[0]], ent[1], ent[2]))
            else:  # a size-1 dim: always index its constant offset
                ents.append(aff(ent[2]))
        return _Val(v.array, out_shape, tuple(ents))

    def _slice(self, v, params, out_shape):
        if isinstance(v, float):
            return v
        starts = tuple(int(x) for x in params["start_indices"])
        strides = params.get("strides") or (1,) * len(starts)
        strides = tuple(int(x) for x in strides)
        ents = []
        for ent in v.spec:
            if isinstance(ent, AffExpr):
                ents.append(ent)
            else:
                d, coef, const = ent
                ents.append((d, coef * strides[d],
                             const + coef * starts[d]))
        return _Val(v.array, out_shape, tuple(ents))

    # -- scan: the recurrence loop ------------------------------------------
    def _scan(self, eqn, invals) -> list:
        pr = eqn.params
        if pr.get("reverse"):
            self._die("scan", "reverse=True")
        T = int(pr["length"])
        n_c, n_k = int(pr["num_consts"]), int(pr["num_carry"])
        body = pr["jaxpr"]
        consts = invals[:n_c]
        inits = invals[n_c:n_c + n_k]
        xs = invals[n_c + n_k:]
        pre_exts = tuple(n for _, n in self.prefix)
        pre_exprs = tuple(e for e, _ in self.prefix)

        carries = []  # (array, logical shape)
        for i, init in enumerate(inits):
            shp = tuple(body.jaxpr.invars[n_c + i].aval.shape)
            cname = self._new_array(pre_exts + (T + 1,) + (shp or (1,)))
            self._emit_nest(shp,
                            lambda els, v=init, s=shp: self._bload(v, els, s),
                            store_arr=cname, store_lead=(aff(0),))
            carries.append((cname, shp))

        ctx = self.b.loop(self._iv("t"), 0, T)
        t = ctx.__enter__()
        self.prefix.append((t, T))
        try:
            benv = list(consts)
            for cname, shp in carries:
                benv.append(_Val(cname, shp,
                                 self._spec(pre_exprs + (t,), shp)))
            for x in xs:
                benv.append(self._bind_time(x, t))
            bouts = self.run(body, benv)
            new_carries = bouts[:n_k]
            ys = bouts[n_k:]
            for (cname, shp), nv in zip(carries, new_carries):
                self._emit_nest(shp,
                                lambda els, v=nv, s=shp:
                                self._bload(v, els, s),
                                store_arr=cname, store_lead=(t + 1,),
                                pre_drop=1)
            y_arrays = []
            for y in ys:
                shp = () if isinstance(y, float) else y.shape
                fresh = (not isinstance(y, float)
                         and y.spec == self._spec(pre_exprs + (t,), shp))
                if fresh:  # already a per-step intermediate: reuse in place
                    y_arrays.append((y.array, shp))
                else:
                    yv = self._emit_nest(
                        shp, lambda els, v=y, s=shp: self._bload(v, els, s))
                    y_arrays.append((yv.array, shp))
        finally:
            self.prefix.pop()
            ctx.__exit__()

        outs = []
        for cname, shp in carries:
            outs.append(_Val(cname, shp,
                             self._spec(pre_exprs + (aff(T),), shp)))
        for yarr, shp in y_arrays:
            outs.append(_Val(yarr, (T,) + shp,
                             self._spec(pre_exprs, (T,) + shp)))
        return outs

    def _bind_time(self, val, t: AffExpr):
        """Bind a scanned input's leading (time) dim to the loop iv."""
        if isinstance(val, float):
            self._die("scan", "scanned-over constant input")
        ents = []
        for ent in val.spec:
            if isinstance(ent, AffExpr):
                ents.append(ent)
            elif ent[0] == 0:
                ents.append(t * ent[1] + ent[2])
            else:
                ents.append((ent[0] - 1, ent[1], ent[2]))
        return _Val(val.array, val.shape[1:], tuple(ents))


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@dataclass
class TracedProgram:
    """A Program built by tracing ``fn`` plus the differential-check hooks.

    ``program`` is ordinary affine IR — feed it straight to ``hls.compile``.
    ``in_names``/``out_names`` name the arrays bound to the function's
    arguments and (copied) outputs; ``in_shapes``/``out_shapes`` keep the
    original JAX shapes (scalars are stored as shape-(1,) arrays)."""

    program: Program
    fn: Callable
    in_names: tuple
    out_names: tuple
    in_shapes: tuple
    out_shapes: tuple

    def validate(self, seed: int = 0, rtol: float = 1e-12) -> float:
        """Differential check: run the traced Program through
        ``sim.sequential_exec`` and ``fn`` (float64) on the same inputs;
        returns the max relative error, raising AssertionError past
        ``rtol``."""
        from . import sim

        inputs = sim.make_inputs(self.program, seed=seed)
        got = sim.sequential_exec(self.program, inputs)
        args = [np.asarray(inputs[n], np.float64).reshape(s)
                for n, s in zip(self.in_names, self.in_shapes)]
        with jax.enable_x64(True):
            want = self.fn(*[jnp.asarray(a) for a in args])
        if not isinstance(want, (tuple, list)):
            want = (want,)
        worst = 0.0
        for name, shape, w in zip(self.out_names, self.out_shapes, want):
            g = np.asarray(got[name], np.float64).reshape(shape)
            w = np.asarray(w, np.float64)
            err = np.max(np.abs(g - w) / np.maximum(np.abs(w), 1e-300))
            worst = max(worst, float(err))
            if not np.allclose(g, w, rtol=rtol, atol=0):
                raise AssertionError(
                    f"traced '{self.program.name}' diverges from its source "
                    f"kernel on '{name}': max rel err {err:.3e} > {rtol:g}")
        return worst

    def lint(self):
        """Static findings on the traced IR (``analysis.lint``) — catches
        what :meth:`validate` cannot: accesses that only leave their array
        on inputs the differential seed never exercises, dead stores the
        simulator silently performs, and multi-writer hazards masked by
        sequential execution order."""
        from .analysis import lint
        return lint(self.program)


def trace(fn: Callable, *example_args, name: Optional[str] = None,
          in_names: Optional[Sequence[str]] = None,
          out_names: Optional[Sequence[str]] = None) -> TracedProgram:
    """Trace ``fn`` on ``example_args`` into a :class:`TracedProgram`."""
    name = name or getattr(fn, "__name__", "traced")
    closed = jax.make_jaxpr(fn)(*example_args)
    if in_names is None:
        try:
            in_names = list(inspect.signature(fn).parameters)
        except (TypeError, ValueError):
            in_names = []
    in_names = list(in_names)
    flat_avals = [v.aval for v in closed.jaxpr.invars]
    if len(in_names) != len(flat_avals):  # pytree args: positional names
        in_names = [f"x{i}" for i in range(len(flat_avals))]
    tr = _Tracer(name)
    invals = []
    in_shapes = []
    for argname, aval in zip(in_names, flat_avals):
        shp = tuple(int(s) for s in aval.shape)
        tr._new_array(shp or (1,), is_arg=True, name=argname)
        invals.append(_Val(argname, shp, tr._spec((), shp)))
        in_shapes.append(shp)
    outs = tr.run(closed, invals)
    if out_names is None:
        out_names = [f"out{i}" for i in range(len(outs))] \
            if len(outs) > 1 else ["out"]
    out_shapes = []
    for oname, val in zip(out_names, outs):
        shp = () if isinstance(val, float) else val.shape
        tr._new_array((tuple(shp) or (1,)), is_arg=True, name=oname)
        tr._emit_nest(shp, lambda els, v=val, s=shp: tr._bload(v, els, s),
                      store_arr=oname)
        out_shapes.append(tuple(shp))
    return TracedProgram(program=tr.b.build(), fn=fn,
                         in_names=tuple(in_names),
                         out_names=tuple(out_names),
                         in_shapes=tuple(in_shapes),
                         out_shapes=tuple(out_shapes))


# ---------------------------------------------------------------------------
# Traced variants of the bundled kernels (single head, tiny shapes)
# ---------------------------------------------------------------------------


def wkv6_program(T: int = 4, D: int = 4) -> TracedProgram:
    """Single-head RWKV-6 WKV recurrence (``kernels.ref.wkv6_ref`` math with
    B=H=1): a ``lax.scan`` over tokens carrying the (D, D) state."""

    def wkv6_head(r, k, v, w, u):
        def step(s, xs):
            rt, kt, vt, wt = xs                       # (D,)
            kv = kt[:, None] * vt[None, :]            # (D, D)
            out = ((s + u[:, None] * kv) * rt[:, None]).sum(axis=0)
            s1 = s * wt[:, None] + kv
            return s1, out

        s0 = jnp.zeros((s0_d, s0_d), r.dtype)
        _, outs = jax.lax.scan(step, s0, (r, k, v, w))
        return outs

    s0_d = D
    ex = [np.zeros((T, D), np.float32)] * 4 + [np.zeros((D,), np.float32)]
    return trace(wkv6_head, *ex, name=f"traced_wkv6_t{T}d{D}")


def conv_block_program(H: int = 8, W: int = 8) -> TracedProgram:
    """Separable 3x3 conv block (``kernels.ref.stencil_pipeline_ref``):
    a row pass then a column pass — the paper's Fig. 1 chain, traced."""

    def conv_block(img, wx, wy):
        bx = (img[:, 0:W - 2] * wx[0] + img[:, 1:W - 1] * wx[1]
              + img[:, 2:W] * wx[2])
        return (bx[0:H - 2, :] * wy[0] + bx[1:H - 1, :] * wy[1]
                + bx[2:H, :] * wy[2])

    ex = [np.zeros((H, W), np.float32), np.zeros((3,), np.float32),
          np.zeros((3,), np.float32)]
    return trace(conv_block, *ex, name=f"traced_conv_h{H}w{W}")


def attention_program(T: int = 4, D: int = 4) -> TracedProgram:
    """Single-head softmax attention (``kernels.ref.flash_attention_ref``
    math, non-causal, B=H=1): two matmuls around a max/sum softmax."""

    def attention(q, k, v):
        s = (q @ k.T) * (D ** -0.5)
        m = s.max(axis=1, keepdims=True)
        e = jnp.exp(s - m)
        z = e.sum(axis=1, keepdims=True)
        return (e / z) @ v

    ex = [np.zeros((T, D), np.float32)] * 3
    return trace(attention, *ex, name=f"traced_attention_t{T}d{D}")
