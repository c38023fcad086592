"""In-program spans and counters (``repro.core.telemetry``): how spans
nest and reduce, what recording off costs, and that recording changes
nothing a compile or a lowering produces."""
import importlib
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import hls, programs, telemetry
from repro.core.codegen import lower_program

# the module, not the function ``repro.core.autotune`` it exports
autotune = importlib.import_module("repro.core.autotune")

# blur_hd's DSE request (bench/configs/blur_hd.json) at its DSE size, 8 x 8
SEARCH = hls.SearchConfig(moves=("fuse", "tile"), unroll_factors=(),
                          tile_sizes=(2, 4), max_candidates=8, cache=False)
OBJECTIVES = (hls.minimize("latency"), hls.minimize("bram"))


def _compile():
    return hls.compile(programs.blur_chain(8, storage="bram"),
                       objectives=OBJECTIVES, search=SEARCH)


class _CountingAnnotation:
    entered = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler
    _CountingAnnotation.entered = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    return _CountingAnnotation


def test_spans_nest_with_parent_and_root_ids():
    with telemetry.recording() as rec:
        with telemetry.span("a"):
            with telemetry.span("b", k=1) as b:
                with telemetry.span("c"):
                    pass
                b.set(hit=True)
            with telemetry.span("d"):
                pass
        with telemetry.span("e"):
            pass
    by = {s.name: s for s in rec.spans}
    assert [s.name for s in rec.spans] == ["a", "b", "c", "d", "e"]
    a, b, c, d, e = (by[n] for n in "abcde")
    assert a.parent is None and a.root == a.id
    assert b.parent == a.id and c.parent == b.id and d.parent == a.id
    assert {b.root, c.root, d.root} == {a.id}
    assert e.parent is None and e.root == e.id != a.id
    assert b.attrs == {"k": 1, "hit": True}
    assert all(s.start_ns <= s.end_ns for s in rec.spans)
    assert c.start_ns >= b.start_ns and c.end_ns <= b.end_ns


def test_self_time_is_duration_less_children():
    rec = telemetry.Record()
    for sid, parent, name, t0, t1 in [(1, None, "root", 0, 1000),
                                      (2, 1, "child", 100, 400),
                                      (3, 1, "child", 500, 600),
                                      (4, 2, "leaf", 150, 250)]:
        s = telemetry.Span(name, {})
        s.id, s.parent, s.root, s.start_ns, s.end_ns = sid, parent, 1, t0, t1
        rec.spans.append(s)
    rec.counters = {"hls.x": 3}
    got = telemetry.summary(rec)
    assert got["counters"] == {"hls.x": 3}
    sp = got["spans"]
    assert sp["root"] == {"count": 1, "total_s": pytest.approx(1000e-9),
                          "self_s": pytest.approx(600e-9)}
    assert sp["child"] == {"count": 2, "total_s": pytest.approx(400e-9),
                           "self_s": pytest.approx(300e-9)}
    assert sp["leaf"]["self_s"] == pytest.approx(100e-9)


def test_counters_change_inside_and_outside_recording():
    before = telemetry.counters.get("test.n", 0)
    telemetry.count("test.n")
    assert telemetry.counters["test.n"] == before + 1
    with telemetry.recording() as rec:
        telemetry.count("test.n", 4)
        telemetry.count("test.m")
    telemetry.count("test.n")
    assert telemetry.counters["test.n"] == before + 6
    assert rec.counters["test.n"] == 4 and rec.counters["test.m"] == 1
    assert rec.spans == []


def test_off_keeps_nothing_and_enters_no_annotation(annotations):
    first = telemetry.span("a")
    with first as s, telemetry.span("b", k=1) as t:
        s.set(x=1)
    assert first is t is telemetry.span("c")   # one shared no-op
    assert annotations.entered == 0
    with telemetry.recording() as rec:
        with telemetry.span("a"), telemetry.span("b"):
            pass
    assert annotations.entered == 2 and len(rec.spans) == 2
    with telemetry.span("after"):
        pass
    assert annotations.entered == 2


def test_recording_does_not_nest():
    with telemetry.recording():
        with pytest.raises(RuntimeError):
            with telemetry.recording():
                pass


def test_off_touches_no_jax():
    """A compile with recording off imports no JAX."""
    code = ("import sys\n"
            "from repro.core import hls, programs, telemetry\n"
            "hls.compile(programs.blur_chain(4), pipeline='fuse')\n"
            "assert telemetry.counters['hls.compiles'] == 1\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    src = str(Path(telemetry.__file__).parents[2])
    env = dict(os.environ, PYTHONPATH=src)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env)
    assert p.returncode == 0, p.stderr


def test_compile_records_one_root_and_a_candidate_per_measurement(
        monkeypatch):
    measured = {"n": 0}
    real = autotune.measure_candidate

    def counted(*a, **k):
        measured["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(autotune, "measure_candidate", counted)
    with telemetry.recording() as rec:
        _compile()
    roots = [s for s in rec.spans if s.parent is None]
    assert [s.name for s in roots] == ["hls.compile"]
    assert {s.root for s in rec.spans} == {roots[0].id}
    cands = [s for s in rec.spans if s.name == "hls.candidate"]
    assert len(cands) == measured["n"] > 1
    assert all(set(s.attrs) == {"desc", "cached"} and not s.attrs["cached"]
               for s in cands)
    assert cands[0].attrs["desc"] == "baseline"
    names = {s.name for s in rec.spans}
    assert {"hls.compile", "hls.lint", "hls.explore", "hls.candidate",
            "hls.pass", "hls.verify", "hls.deps", "hls.ii_search",
            "hls.schedule", "hls.resources", "hls.static_check"} <= names
    passes = [s for s in rec.spans if s.name == "hls.pass"]
    assert passes and all("name" in s.attrs for s in passes)
    sm = telemetry.summary(rec)
    assert sm["spans"]["hls.compile"]["count"] == 1
    assert sm["counters"]["hls.compiles"] == 1
    # the closed form takes every case of the tiled candidates by branching
    assert sm["counters"]["hls.dep_cases_branched"] > 0
    assert sm["counters"].get("hls.dep_cases_ilp", 0) == \
        sm["spans"].get("hls.dep_ilp", {}).get("count", 0) == 0


def test_each_ilp_case_records_one_dep_ilp_span(wide_coupled_program):
    with telemetry.recording() as rec:
        autotune.compile_program(wide_coupled_program)
    sm = telemetry.summary(rec)
    assert sm["counters"]["hls.dep_cases_ilp"] > 0
    assert sm["counters"]["hls.dep_cases_ilp"] == \
        sm["spans"]["hls.dep_ilp"]["count"]


def test_ii_probes_count_the_feasible_calls(monkeypatch):
    calls = {"n": 0}
    real = autotune.feasible

    def counted(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(autotune, "feasible", counted)
    before = telemetry.counters.get("hls.ii_probes", 0)
    _compile()
    assert telemetry.counters["hls.ii_probes"] - before == calls["n"] > 0


def _frontier(r):
    return ([(c.desc, c.latency) for c in r.frontier], r.best.desc,
            [(c.desc, c.latency, c.status) for c in r.candidates])


def test_compile_result_identical_with_recording_on_and_off():
    off = _compile()
    with telemetry.recording():
        on = _compile()
    assert _frontier(on) == _frontier(off)


def test_lowered_source_identical_with_recording_on_and_off():
    p = programs.blur_chain(16, storage="bram")
    off = lower_program(p, block_rows=8)
    with telemetry.recording() as rec:
        on = lower_program(p, block_rows=8)
    assert on.source == off.source
    lower = [s for s in rec.spans if s.name == "hls.lower"]
    assert len(lower) == 1
    kids = [s.name for s in rec.spans if s.parent == lower[0].id]
    assert kids == ["hls.lint", "hls.lower.extract", "hls.lower.plan",
                    "hls.lower.emit", "hls.lower.exec"]
