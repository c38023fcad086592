"""The per-layer metrics read from the program's own spans
(``bench/counters.py``, ``Readings.program``): the divisor, 0.0 for a span
that a recorded compile never opened, nothing without a record; and the
runs that record them: a traced run of the recompile mix, and no
untraced run."""
import sys

import pytest

from bench import drive, peaks, run
from bench import spec as bspec
from bench.tests.sizes import small
from bench.tests.test_faults import CELLS, SEED, SPEC
from repro.core import telemetry

READERS = {"ii_search_s": "hls.ii_search", "dep_ilp_s": "hls.dep_ilp",
           "verify_s": "hls.verify"}


def _readings(program):
    return run.Readings(calls=1, trace=None, spans={}, ops=0, nbytes=0,
                        peak=None, program=program)


def _program(spans, compiles):
    return {"spans": {k: {"count": 1, "total_s": v, "self_s": v}
                      for k, v in spans.items()},
            "counters": {}, "compiles": compiles}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_divides_by_the_compiles(metric):
    p = _program({READERS[metric]: 1.5, "hls.other": 9.0}, 4)
    assert bspec.load_reader(metric)(_readings(p)) == pytest.approx(0.375)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_zero_for_a_span_never_opened(metric):
    p = _program({"hls.compile": 0.5}, 2)
    assert bspec.load_reader(metric)(_readings(p)) == 0.0


@pytest.mark.parametrize("metric", sorted(READERS))
@pytest.mark.parametrize("program", [None, _program({"hls.ii_search": 1.0,
                                                     "hls.dep_ilp": 1.0,
                                                     "hls.verify": 1.0}, 0)],
                         ids=["no_record", "no_compile"])
def test_reader_reads_nothing_without_a_recorded_compile(metric, program):
    assert bspec.load_reader(metric)(_readings(program)) is None


def test_program_readings_count_root_compiles():
    """The divisor is the root ``hls.compile`` spans: one opened inside
    another span is not a compile of its own."""
    with telemetry.recording() as rec:
        for _ in range(2):
            with telemetry.span("hls.compile"):
                with telemetry.span("hls.ii_search"):
                    pass
        with telemetry.span("bench.outer"):
            with telemetry.span("hls.compile"):
                pass
    p = run.program_readings(rec)
    assert p["compiles"] == 2
    assert p["spans"]["hls.ii_search"]["count"] == 2
    assert run.program_readings(None) is None


def _run(cell_name, trace, seconds=0.2):
    cell = bspec.cell(SPEC, cell_name)
    return run.run_cell(SPEC, cell, SEED, seconds, trace, t0=0.0,
                        cfg_override=small(cell["config"]), interpret=True)


def _kept_readings(monkeypatch):
    """The ``Readings`` a traced run hands its readers.  The CPU has no
    peaks, and the recompile cell's readers read none."""
    monkeypatch.setattr(peaks, "peak_of", lambda kind: None)
    kept = []
    cls = run.Readings

    def readings(**kw):
        kept.append(cls(**kw))
        return kept[-1]

    monkeypatch.setattr(run, "Readings", readings)
    return kept


def test_traced_recompile_run_reports_the_span_metrics(monkeypatch):
    kept = _kept_readings(monkeypatch)
    result, info = _run("blur_hd.recompile", True)
    assert result["correct"], result
    program = kept[0].program
    # the window's compiles and the traced stretch's one
    assert program["compiles"] == result["attempted"] + 1
    assert program["spans"]["hls.compile"]["count"] == program["compiles"]
    for m, span in READERS.items():
        assert result["metrics"][m]["value"] == pytest.approx(
            program["spans"].get(span, {}).get("total_s", 0.0)
            / program["compiles"])
        assert result["metrics"][m]["unit"] == "s"
    assert result["metrics"]["ii_search_s"]["value"] > 0
    assert info["program_spans"]["hls.compile"] > 0
    assert "idle_self" in result["breakdown"]
    assert not telemetry._on


def test_traced_run_without_program_telemetry_completes(monkeypatch):
    """A program that keeps no telemetry, as an older one: no record, no
    span metric, and the run is still checked."""
    import repro.core
    kept = _kept_readings(monkeypatch)
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    result, info = _run("blur_hd.recompile", True)
    assert result["correct"], result
    assert kept[0].program is None
    assert not set(READERS) & set(result["metrics"])
    assert "program_spans" not in info


@pytest.mark.parametrize("cell_name", CELLS)
def test_untraced_run_records_no_span(cell_name, monkeypatch):
    """The window of a run whose end-to-end metrics count never records:
    recording stays off throughout, and is never entered."""
    seen = []

    def watched(fn):
        def call(*a, **k):
            seen.append(telemetry._on)
            return fn(*a, **k)
        return call

    def refused():
        raise AssertionError("an untraced run entered telemetry.recording()")

    monkeypatch.setattr(drive, "compile_once", watched(drive.compile_once))
    monkeypatch.setattr(drive, "stream", watched(drive.stream))
    monkeypatch.setattr(telemetry, "recording", refused)
    result, _ = _run(cell_name, False)
    assert result["correct"], result
    assert seen and not any(seen)
