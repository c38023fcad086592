"""Persistent compile cache + serving-scale DSE (DESIGN.md §8).

Covers, per the serving-scale-DSE acceptance criteria:

  * fingerprint sensitivity: any program / pipeline / mode / salt mutation
    changes the key (property over randomized programs), while rebuilding
    the same program (fresh uids) does not;
  * positional schedule round-trip onto a structurally identical program
    with different uids;
  * cold-vs-warm byte identity of candidates, schedules and whole
    frontiers, in-process and against a store written by this process;
  * corrupted and stale (salt-mismatch) entries are detected, discarded,
    and transparently recompiled;
  * concurrent writers never corrupt the store (atomic replace);
  * LRU eviction bounds the store;
  * parallel (jobs=2) expansion is bit-identical to serial;
  * macro-moves reach the blur_chain fuse+tile frontier point in strictly
    fewer compiles than the classic max_candidates=24 search;
  * the hypervolume selector is deterministic and exact on knowns;
  * deps.cache_stats() exposes the bounded data-pair cache counters,
    read from the process-wide telemetry counters.
"""
import json
import multiprocessing
import os

import pytest

from repro.core import deps, hls, telemetry
from repro.core.autotune import (_hv, measure_candidate, pareto_explore)
from repro.core.cache import (CacheStore, SCHEDULER_SALT, fingerprint,
                              get_store, pack_schedule, program_text,
                              string_key, unpack_schedule)
from repro.core.autotune import compile_program
from repro.core.programs import blur_chain, conv_pool, two_mm
from repro.core.transforms import Normalize
from test_property import random_program


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A fresh persistent store in a tmpdir, with the global cache enabled
    for this test only (the suite-wide conftest default is off)."""
    monkeypatch.setenv("REPRO_HLS_CACHE", "1")
    monkeypatch.setenv("REPRO_HLS_CACHE_DIR", str(tmp_path / "cache"))
    st = get_store()
    assert st is not None
    return st


def _explore(p, store, **kw):
    kw.setdefault("rel_caps", {"bram_bytes": 1.0, "dsp": 1.0})
    kw.setdefault("max_candidates", 12)
    return pareto_explore(p, store=store, **kw)


def _result_sig(r):
    """Everything observable about a ParetoResult, schedules included."""
    def cand(c):
        return (c.desc, c.latency, dict(c.res), c.status, c.within_budget,
                sorted(c.schedule.iis.values()),
                sorted(c.schedule.theta.values()))
    return ([cand(c) for c in r.candidates], [cand(c) for c in r.frontier],
            r.rejected, r.caps, r.compiles)


# ---------------------------------------------------------------------------
# Fingerprint sensitivity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_fingerprint_stable_across_rebuilds(seed):
    """Rebuilding the same program (fresh process-local uids) yields the
    same fingerprint — the property that makes cross-process reuse work."""
    a, b = random_program(seed), random_program(seed)
    assert program_text(a) == program_text(b)
    assert fingerprint(a) == fingerprint(b)


def test_fingerprint_distinguishes_random_programs():
    keys = [fingerprint(random_program(s)) for s in range(10)]
    assert len(set(keys)) == len(keys)


def test_fingerprint_sensitive_to_every_input():
    p = blur_chain(8, storage="bram")
    base = fingerprint(p)
    # pipeline text, resource mode, salt, caller-extra all key separately
    assert fingerprint(p, pipeline="fuse") != base
    assert fingerprint(p, mode="vitis_seq") != base
    assert fingerprint(p, salt="other-compiler-version") != base
    assert fingerprint(p, extra="frontier") != base
    # program mutations: bounds, pragmas, array metadata, op latencies
    q = blur_chain(8, storage="bram")
    q.body[0].ub += 1
    assert fingerprint(q) != base
    q2 = blur_chain(8, storage="bram")
    q2.body[0].ii = 3
    assert fingerprint(q2) != base
    assert fingerprint(blur_chain(8, storage="reg")) != base
    assert fingerprint(blur_chain(16, storage="bram")) != base
    q3 = blur_chain(8, storage="bram")
    q3.op_delays = dict(q3.op_delays, mul=7)
    assert fingerprint(q3) != base


# ---------------------------------------------------------------------------
# Positional schedule round-trip
# ---------------------------------------------------------------------------


def test_schedule_roundtrip_across_rebuild():
    p = two_mm(4)
    s = compile_program(p)
    blob = json.loads(json.dumps(pack_schedule(s)))   # through JSON, as disk
    q = two_mm(4)                                     # fresh uids
    s2 = unpack_schedule(q, blob)
    assert s2.feasible
    assert sorted(s.iis.values()) == sorted(s2.iis.values())
    assert sorted(s.theta.values()) == sorted(s2.theta.values())
    assert s.completion_time() == s2.completion_time()
    assert len(s.edges) == len(s2.edges)


def test_schedule_unpack_rejects_mismatched_program():
    s = compile_program(two_mm(4))
    blob = pack_schedule(s)
    with pytest.raises(ValueError):
        unpack_schedule(blur_chain(8), blob)


# ---------------------------------------------------------------------------
# Cold vs warm byte identity
# ---------------------------------------------------------------------------


def test_cold_warm_identity_same_process(store):
    cold = _explore(blur_chain(8, storage="bram"), store)
    warm = _explore(blur_chain(8, storage="bram"), store)
    assert _result_sig(cold) == _result_sig(warm)
    assert not any(c.cached for c in cold.candidates)
    assert all(c.cached for c in warm.candidates)


def test_cold_warm_identity_fresh_store_view(store):
    """A second CacheStore over the same directory (simulating a new
    process: empty in-memory layer, different uids via rebuild) serves the
    identical frontier from disk."""
    cold = _explore(conv_pool(8, storage="bram"), store)
    fresh = CacheStore(store.root)
    warm = _explore(conv_pool(8, storage="bram"), fresh)
    assert _result_sig(cold) == _result_sig(warm)
    assert all(c.cached for c in warm.candidates)
    assert fresh.hits >= 1 and fresh.puts == 0


def test_candidate_noop_is_cached(store):
    p = blur_chain(8)
    assert measure_candidate(p, "normalize", [Normalize()], store=store) is None
    misses = store.misses
    assert measure_candidate(p, "normalize", [Normalize()], store=store) is None
    assert store.misses == misses          # served from the cache


def test_explain_reports_cache_hits(store, monkeypatch):
    p = blur_chain(8, storage="bram")
    sc = hls.SearchConfig(max_candidates=6, unroll_factors=(2,),
                          tile_sizes=(2,))
    cold = hls.compile(p, search=sc)
    warm = hls.compile(blur_chain(8, storage="bram"), search=sc)
    assert "{cache hit}" not in cold.explain()
    assert "{cache hit}" in warm.explain()
    assert [c.desc for c in warm.frontier] == [c.desc for c in cold.frontier]


def test_unverified_entries_do_not_serve_verified_requests(store):
    p = two_mm(4)
    r1 = _explore(p, store, verify=False)
    r2 = _explore(two_mm(4), store, verify=True)    # must NOT reuse
    assert not any(c.cached for c in r2.candidates)
    r3 = _explore(two_mm(4), store, verify=True)    # now it may
    assert all(c.cached for c in r3.candidates)
    assert _result_sig(r1) == _result_sig(r2) == _result_sig(r3)


# ---------------------------------------------------------------------------
# Corruption / staleness
# ---------------------------------------------------------------------------


def _entry_files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs
            if f.endswith(".json")]


def test_corrupt_entries_are_discarded_and_recompiled(store):
    cold = _explore(blur_chain(8, storage="bram"), store)
    files = _entry_files(store.root)
    assert files
    for path in files:
        with open(path, "w") as f:
            f.write('{"truncated": ')
    fresh = CacheStore(store.root)
    again = _explore(blur_chain(8, storage="bram"), fresh)
    assert _result_sig(cold) == _result_sig(again)
    assert not any(c.cached for c in again.candidates)
    assert fresh.misses > 0 and fresh.hits == 0


def test_salt_mismatch_invalidates(store):
    """Entries written by a different compiler version (salt) are stale by
    definition: detected, deleted, recompiled."""
    cold = _explore(blur_chain(8, storage="bram"), store)
    old = CacheStore(store.root, salt="repro-hls-ancient")
    again = _explore(blur_chain(8, storage="bram"), old)
    assert _result_sig(cold) == _result_sig(again)
    assert not any(c.cached for c in again.candidates)
    # and the store now serves the NEW salt's entries
    warm = _explore(blur_chain(8, storage="bram"),
                    CacheStore(store.root, salt="repro-hls-ancient"))
    assert all(c.cached for c in warm.candidates)


# ---------------------------------------------------------------------------
# Concurrent writers / eviction
# ---------------------------------------------------------------------------


def _hammer_store(args):
    root, wid = args
    st = CacheStore(root)
    for i in range(40):
        st.put(string_key("contended", str(i % 8)),
               {"writer": wid, "i": i, "pad": "x" * 256})
    return wid


def test_concurrent_writers_do_not_corrupt(store):
    with multiprocessing.Pool(4) as pool:
        pool.map(_hammer_store, [(store.root, w) for w in range(4)])
    # every surviving file is a complete, valid wrapper (atomic replace:
    # last writer wins, torn writes are impossible)
    files = _entry_files(store.root)
    assert len(files) == 8
    for path in files:
        with open(path) as f:
            wrapper = json.load(f)
        assert wrapper["salt"] == SCHEDULER_SALT
        assert wrapper["data"]["i"] >= 0
    fresh = CacheStore(store.root)
    for i in range(8):
        assert fresh.get(string_key("contended", str(i))) is not None


def test_lru_eviction_bounds_the_store(tmp_path):
    st = CacheStore(str(tmp_path / "c"), max_entries=8)
    for i in range(40):
        st.put(string_key("evict", str(i)), {"i": i})
    st.sweep()
    assert len(_entry_files(st.root)) <= 8
    assert st.evictions >= 32


# ---------------------------------------------------------------------------
# Parallel expansion determinism
# ---------------------------------------------------------------------------


def test_parallel_bit_identical_to_serial():
    for make in (blur_chain, conv_pool):
        serial = pareto_explore(make(8), rel_caps={"bram_bytes": 1.5,
                                                   "dsp": 4.0},
                                max_candidates=10, store=None)
        par = pareto_explore(make(8), rel_caps={"bram_bytes": 1.5,
                                                "dsp": 4.0},
                             max_candidates=10, store=None, jobs=2)
        assert _result_sig(serial) == _result_sig(par)


def test_parallel_with_cache_interplay(store):
    cold = _explore(blur_chain(8, storage="bram"), store, jobs=2)
    warm = _explore(blur_chain(8, storage="bram"), store, jobs=2)
    assert _result_sig(cold) == _result_sig(warm)
    assert all(c.cached for c in warm.candidates)


# ---------------------------------------------------------------------------
# Macro-moves + hypervolume selector
# ---------------------------------------------------------------------------


def test_macro_moves_reach_fuse_tile_in_fewer_compiles():
    """Acceptance: the composite fuse>tile step reaches the blur_chain
    fuse+tile frontier point in strictly fewer compiles than the classic
    one-move-at-a-time max_candidates=24 search."""
    caps = {"bram_bytes": 1.0, "dsp": 1.0}
    classic = pareto_explore(blur_chain(8), rel_caps=caps,
                             max_candidates=24, store=None)
    assert any("fuse" in c.desc and "tile" in c.desc
               for c in classic.frontier)
    macro = pareto_explore(blur_chain(8), rel_caps=caps, max_candidates=6,
                           macro_moves=True, store=None)
    assert any(c.desc.startswith("fuse>tile") for c in macro.frontier)
    assert macro.compiles < classic.compiles
    # the macro point matches the classic fuse|tile point exactly
    classic_pt = next(c for c in classic.frontier
                      if "fuse" in c.desc and "tile" in c.desc)
    macro_pt = next(c for c in macro.frontier
                    if c.desc.startswith("fuse>tile"))
    assert macro_pt.objectives() == classic_pt.objectives()


def test_hv_selector_deterministic():
    kw = dict(rel_caps={"bram_bytes": 1.5, "dsp": 4.0}, max_candidates=10,
              selector="hv", macro_moves=True, store=None)
    a = pareto_explore(blur_chain(8), **kw)
    b = pareto_explore(blur_chain(8), **kw)
    assert _result_sig(a) == _result_sig(b)
    from repro.core.autotune import dominates
    for c in a.frontier:
        assert not any(dominates(d.objectives(), c.objectives())
                       for d in a.frontier if d is not c)


def test_hv_exact_on_knowns():
    # two staircase points, union of boxes = 3.0
    assert _hv([(0.0, 1.0), (1.0, 0.0)], (2.0, 2.0)) == pytest.approx(3.0)
    # dominated point adds nothing
    assert _hv([(0.0, 1.0), (1.0, 0.0), (1.0, 1.0)],
               (2.0, 2.0)) == pytest.approx(3.0)
    # point outside the reference contributes nothing
    assert _hv([(3.0, 3.0)], (2.0, 2.0)) == 0.0
    # 3D sanity: single point
    assert _hv([(0.5, 0.5, 0.5)], (1.0, 1.0, 1.0)) == pytest.approx(0.125)


def test_unknown_selector_rejected():
    with pytest.raises(ValueError, match="unknown selector"):
        pareto_explore(two_mm(4), selector="random", store=None)


# ---------------------------------------------------------------------------
# deps data-pair cache stats
# ---------------------------------------------------------------------------


def test_deps_cache_stats_counters():
    stats0 = deps.cache_stats()
    assert stats0["max_entries"] == 64
    p = blur_chain(8)
    deps.DepAnalysis(p)
    mid = deps.cache_stats()
    deps.DepAnalysis(p)   # same uids + spaces: served from the shared cache
    after = deps.cache_stats()
    assert after["hits"] >= mid["hits"] + 1
    assert after["misses"] == mid["misses"]
    assert after["entries"] <= after["max_entries"]
    # the stats read the process-wide counters
    assert after["hits"] == telemetry.counters["hls.data_pairs_shared"]
    assert after["misses"] == telemetry.counters["hls.data_pairs_enumerated"]


# ---------------------------------------------------------------------------
# Kill switch
# ---------------------------------------------------------------------------


def test_cache_disabled_by_env(monkeypatch):
    monkeypatch.setenv("REPRO_HLS_CACHE", "0")
    assert get_store() is None
