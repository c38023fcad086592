"""The reader of ``phase_planes``: the program's counter
``hls.phase_planes`` per lowering, what a lowering of each configuration
at its CPU test size counts, and nothing read where the program lowered
nothing or keeps no such counter."""
import sys

import pytest

from bench import run
from bench import spec as bspec
from bench.tests.sizes import small
from repro.core import telemetry
from repro.core.codegen import lower_program

READ = bspec.load_reader("phase_planes")
READINGS = run.Readings(calls=1, trace=None, spans={}, ops=0, nbytes=0,
                        peak=None)
SPEC = bspec.load_spec()
STREAMED = sorted({w["config"] for w in SPEC["workloads"]
                   if w["name"] in next(m for m in SPEC["per_layer"]
                                        if m["name"] == "phase_planes")
                   ["workloads"]})


def test_reader_divides_by_the_lowerings(monkeypatch):
    monkeypatch.setattr(telemetry, "counters",
                        {"hls.lowers": 4, "hls.phase_planes": 10,
                         "hls.compiles": 99})
    assert READ(READINGS) == pytest.approx(2.5)


def test_reader_reads_zero_from_a_lowering_that_splits_nothing(monkeypatch):
    monkeypatch.setattr(telemetry, "counters", {"hls.lowers": 2,
                                                "hls.phase_planes": 0})
    assert READ(READINGS) == 0


@pytest.mark.parametrize("counters", [
    {"hls.phase_planes": 10, "hls.compiles": 1},
    {"hls.lowers": 2, "hls.lane_takes": 8, "hls.edge_pad_bytes": 64},
], ids=["no-lowering", "no-phase-counter"])
def test_reader_reads_nothing_without_a_lowering_or_the_counter(
        counters, monkeypatch):
    """A program that lowered nothing, or one from before the counter
    whose lowerings count the other codegen counters, reads None and does
    not raise."""
    monkeypatch.setattr(telemetry, "counters", counters)
    assert READ(READINGS) is None


def test_reader_reads_nothing_from_a_program_without_counters(monkeypatch):
    import repro.core
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert READ(READINGS) is None


@pytest.mark.parametrize("config", STREAMED)
def test_a_lowering_at_the_test_size_reads_its_planes(config, monkeypatch):
    """Lowered at its CPU test size, the configuration's program reads as
    many planes as its kernel takes operands that are no input whole."""
    cfg, mod = bspec.load_config(config)
    cfg = {**cfg, **small(config)}
    p = mod.program(cfg, mod.consts(cfg))
    monkeypatch.setattr(telemetry, "counters", {})
    k = lower_program(p, dtype=cfg["dtype"])
    call = k.source[k.source.index("def run("):].split("    )(")[1]
    operands = call.split(")")[0].split(", ")
    planes = [o for o in operands if o[2:] not in p.arrays]
    assert planes
    assert READ(READINGS) == len(planes)
