"""Differential tests: closed-form affine slack fast path ≡ branch-and-bound ILP.

``DepAnalysis(p, crosscheck=True)`` re-solves EVERY case the fast path takes
with the reference ILP and raises on any mismatch, so driving a full
autotune+schedule under crosscheck exercises the equivalence across all the
II assignments the binary search probes.  We additionally check that the
fast and ILP analyses agree on which pairs/cases are feasible at all (an
II-independent property the fast path must also get right).
"""
import functools
import re

import numpy as np
import pytest

from repro.core import deps, hls, programs, telemetry
from repro.core.autotune import autotune
from repro.core.deps import DepAnalysis
from repro.core.programs import (BENCHMARKS, fig1_conv_chain, fig3_conv1d)
from repro.core.scheduler import schedule


def _differential(p, require_no_fallback=False):
    # crosscheck=True re-solves EVERY fast-path case with the ILP and raises
    # on mismatch — including the None (case-infeasible) decisions made
    # during pair enumeration, so feasibility agreement is covered too.
    dep = DepAnalysis(p, crosscheck=True)
    iis = autotune(p, dep)
    s = schedule(p, iis, dep)
    assert s.feasible
    assert dep.fast_cases > 0
    if require_no_fallback:
        assert dep.fallback_cases == 0, \
            "corpus dependence ILPs must all be closed-form solvable"
    return dep


def _corpus(n):
    progs = [("fig3", fig3_conv1d()), ("fig1", fig1_conv_chain(n=n))]
    for name, mk in BENCHMARKS.items():
        for storage in ("reg", "bram"):
            arg = max(4, n // 2) if name == "two_mm" else n
            progs.append((f"{name}[{arg},{storage}]", mk(arg, storage)))
    return progs


@pytest.mark.parametrize("name,p", _corpus(6), ids=lambda v: v if isinstance(v, str) else "")
def test_corpus_fastpath_matches_ilp(name, p):
    _differential(p, require_no_fallback=True)


@pytest.mark.slow
@pytest.mark.parametrize("name,p", _corpus(32), ids=lambda v: v if isinstance(v, str) else "")
def test_corpus_fastpath_matches_ilp_fullsize(name, p):
    _differential(p, require_no_fallback=True)


# ---------------------------------------------------------------------------
# tiled loops: coupled cases solved by branching on the narrowest box
# ---------------------------------------------------------------------------

# blur_hd's and two_mm_medium's DSE request (bench/configs/)
_SEARCH = hls.SearchConfig(moves=("fuse", "tile"), unroll_factors=(),
                           tile_sizes=(2, 4), max_candidates=8, cache=False)


@functools.lru_cache(maxsize=None)
def _explored(name):
    """The programs the DSE explores, by description; the fused loop's
    uid suffix (``bxi_f13``) is dropped, as it depends on the process."""
    p = {"blur_chain": lambda: programs.blur_chain(8, storage="bram"),
         "two_mm": lambda: programs.two_mm(6, "bram")}[name]()
    r = hls.compile(p, objectives=(hls.minimize("latency"),
                                   hls.minimize("bram")), search=_SEARCH)
    return {re.sub(r"_f\d+", "", c.desc): c.program for c in r.candidates}


@pytest.mark.parametrize("name,desc", [
    ("blur_chain", "tile(bxi:2,byi:2)"),
    ("blur_chain", "tile(byi:4)"),
    ("blur_chain", "fuse | tile(bxi:2)"),
    ("blur_chain", "fuse | tile(bxi:4)"),
    ("two_mm", "tile(ci:2,pi:2)"),
])
def test_tiled_cases_close_by_branching(name, desc):
    # a split index 2*t + b couples four variables in one address row; the
    # intra-tile boxes (2 or 4 values) are narrow enough to branch on
    before = telemetry.counters.get("hls.dep_cases_branched", 0)
    _differential(_explored(name)[desc], require_no_fallback=True)
    assert telemetry.counters.get("hls.dep_cases_branched", 0) > before


def test_coupled_case_past_the_budget_stays_with_the_ilp(
        wide_coupled_program):
    p = wide_coupled_program
    dep = _differential(p)
    assert dep.fallback_cases > 0
    ref = DepAnalysis(p, fastpath=False)
    iis = autotune(p, dep)
    assert dep.memory_edges(iis) == ref.memory_edges(iis)


def _random_coupled_system(seed):
    """Box-bounded integer variables under 1-3 equality rows of 3-5
    variables each: components the two-variable closed form cannot take."""
    rng = np.random.default_rng(9000 + seed)
    nv = int(rng.integers(3, 6))
    vars = {}
    for v in range(nv):
        lo = int(rng.integers(-3, 2))
        vars[v] = (lo, lo + int(rng.integers(0, 5)), int(rng.integers(-4, 5)))
    point = {v: int(rng.integers(lo, hi + 1))
             for v, (lo, hi, _) in vars.items()}
    rows = []
    for _ in range(int(rng.integers(1, 4))):
        cols = rng.choice(nv, size=int(rng.integers(3, nv + 1)),
                          replace=False)
        coeffs = {int(v): int(rng.choice([-3, -2, -1, 1, 2, 3]))
                  for v in cols}
        # feasible through ``point`` on most seeds, infeasible on the rest
        rhs = sum(a * point[v] for v, a in coeffs.items())
        rows.append((coeffs, rhs + int(seed % 5 == 0)))
    return vars, rows


@pytest.mark.parametrize("seed", range(40))
def test_branching_matches_enumeration(seed):
    import itertools

    vars, rows = _random_coupled_system(seed)
    best = None
    keys = sorted(vars)
    for pt in itertools.product(*(range(lo, hi + 1)
                                  for lo, hi, _ in (vars[k] for k in keys))):
        x = dict(zip(keys, pt))
        if all(sum(a * x[v] for v, a in c.items()) == e for c, e in rows):
            val = sum(vars[k][2] * x[k] for k in keys)
            best = val if best is None else min(best, val)
    got, _ = deps._solve_separable(vars, rows)
    assert got is not deps._FALLBACK
    assert got == best


def test_branch_budget_leaves_wide_components_to_the_ilp():
    # four variables coupled by one row, every box wider than the budget
    wide = deps._BRANCH_CAP
    vars = {v: (0, wide, 1) for v in range(4)}
    rows = [({0: wide + 1, 1: 1, 2: -(wide + 1), 3: -1}, 0)]
    assert deps._solve_separable(vars, rows)[0] is deps._FALLBACK
    # one narrow box closes it: pin its 2 values, each branch is 3 vars
    # with another narrow box below the budget left
    vars[1] = (0, 1, 1)
    vars[3] = (0, 1, 1)
    assert deps._solve_separable(vars, rows) == (0, True)


# ---------------------------------------------------------------------------
# randomized affine programs: strides, diagonals, constants, carried deps
# ---------------------------------------------------------------------------


def _random_affine_program(seed: int):
    from repro.core.ir import ProgramBuilder

    rng = np.random.default_rng(2000 + seed)
    b = ProgramBuilder(f"aff{seed}")
    size = int(rng.integers(3, 6))
    n_arrays = int(rng.integers(2, 4))
    names = []
    for a in range(n_arrays):
        full = bool(rng.integers(0, 2))
        b.array(f"A{a}", (2 * size + 3, 2 * size + 3),
                partition=(0, 1) if full else (0,),
                ports=("w", "r") if full else ("w", "r", "r"))
        names.append(f"A{a}")

    def rnd_index(ivs):
        """Random affine expr over the loop ivs: strided, diagonal, shifted,
        or constant — the index shapes the closed form must cover."""
        kind = int(rng.integers(0, 5))
        if kind == 0:            # plain shifted iv
            return ivs[int(rng.integers(0, len(ivs)))] + int(rng.integers(0, 3))
        if kind == 1:            # strided (the DUS decimation pattern)
            return ivs[int(rng.integers(0, len(ivs)))] * 2 + int(rng.integers(0, 2))
        if kind == 2 and len(ivs) > 1:   # diagonal coupling
            return ivs[0] + ivs[1]
        if kind == 3:            # constant address
            return int(rng.integers(0, size))
        return ivs[int(rng.integers(0, len(ivs)))]

    n_nests = int(rng.integers(2, 4))
    for t in range(n_nests):
        src = names[int(rng.integers(0, len(names)))]
        dst = names[int(rng.integers(0, len(names)))]
        depth = int(rng.integers(1, 4))
        ivnames = [f"t{t}l{d}" for d in range(depth)]

        def body(ivs):
            x = b.load(src, rnd_index(ivs), rnd_index(ivs))
            y = b.load(src, rnd_index(ivs), rnd_index(ivs))
            v = b.arith(["add", "mul", "sub"][int(rng.integers(0, 3))], x, y)
            b.store(dst, v, rnd_index(ivs), rnd_index(ivs))

        def nest(d, ivs):
            if d == depth:
                body(ivs)
                return
            with b.loop(ivnames[d], 0, size) as iv_:
                nest(d + 1, ivs + [iv_])

        nest(0, [])
    return b.build()


@pytest.mark.parametrize("seed", range(50))
def test_random_affine_fastpath_matches_ilp(seed):
    p = _random_affine_program(seed)
    _differential(p)


# ---------------------------------------------------------------------------
# randomized imperfect / multi-loop tasks (the generalized nest contract):
# loop-adjacent ops and scan-style recurrences must hit the same closed forms
# ---------------------------------------------------------------------------


def _random_imperfect_program(seed: int):
    """Outer loop holding a loose scalar prologue (load+arith) feeding an
    inner nest — the shape ``ir.nest_shape`` classifies as ``imperfect``."""
    from repro.core.ir import ProgramBuilder

    rng = np.random.default_rng(7000 + seed)
    T, N = int(rng.integers(3, 6)), int(rng.integers(3, 6))
    b = ProgramBuilder(f"imp{seed}")
    b.array("X", (T + 1, N + 2), partition=(0,), ports=("w", "r", "r"))
    b.array("Y", (T + 1, N + 2), partition=(0,), ports=("w", "r", "r"))
    with b.loop("t", 0, T) as t:
        m = b.load("X", t, int(rng.integers(0, N)))
        if rng.integers(0, 2):
            m = b.mul(m, b.const(float(rng.integers(1, 4))))
        with b.loop("j", 0, N) as j:
            v = b.add(b.load("X", t + int(rng.integers(0, 2)), j), m)
            b.store("Y", v, t + int(rng.integers(0, 2)), j)
        if rng.integers(0, 2):  # loose epilogue store after the nest
            b.store("Y", m, t, N + 1)
    return b.build()


def _random_multiloop_program(seed: int):
    """Scan-style task: a time loop whose body holds 2-3 sibling inner
    nests coupled through a carried state array (``multi_loop`` kind)."""
    from repro.core.ir import ProgramBuilder

    rng = np.random.default_rng(8000 + seed)
    T, N = int(rng.integers(3, 5)), int(rng.integers(3, 6))
    b = ProgramBuilder(f"ml{seed}")
    b.array("S", (T + 1, N), partition=(0,), ports=("w", "r", "r"))
    b.array("X", (T, N), partition=(0,), ports=("w", "r", "r"))
    b.array("Y", (T, N), partition=(0,), ports=("w", "r", "r"))
    with b.loop("j0", 0, N) as j:
        b.store("S", b.load("X", 0, j), 0, j)
    with b.loop("t", 0, T) as t:
        with b.loop("j1", 0, N) as j:
            up = b.arith(["add", "mul"][int(rng.integers(0, 2))],
                         b.load("S", t, j), b.load("X", t, j))
            b.store("S", up, t + 1, j)
        with b.loop("j2", 0, N) as j:
            rd = t + 1 if rng.integers(0, 2) else t
            b.store("Y", b.mul(b.load("S", rd, j), b.load("X", t, j)), t, j)
        if rng.integers(0, 2):  # third sibling nest reading the output back
            with b.loop("j3", 0, N) as j:
                b.store("Y", b.add(b.load("Y", t, j), b.const(1.0)), t, j)
    return b.build()


@pytest.mark.parametrize("seed", range(25))
def test_random_imperfect_fastpath_matches_ilp(seed):
    from repro.core.ir import nest_shape

    p = _random_imperfect_program(seed)
    assert nest_shape(p).kinds == ("imperfect",)
    _differential(p)


@pytest.mark.parametrize("seed", range(25))
def test_random_multiloop_fastpath_matches_ilp(seed):
    from repro.core.ir import nest_shape

    p = _random_multiloop_program(seed)
    assert "multi_loop" in nest_shape(p).kinds
    _differential(p)
