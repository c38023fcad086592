"""The per-layer metrics read from the program's own counters
(``bench/counters.py``): the divisor, and nothing read where the program
keeps no such counters."""
import sys

import numpy as np
import pytest

from bench import run
from bench import spec as bspec
from repro.core import hls, telemetry

READERS = {"ii_probes": "hls.ii_probes", "dep_ilp_cases": "hls.dep_cases_ilp"}
READINGS = run.Readings(calls=1, trace=None, spans={}, ops=0, nbytes=0,
                        peak=None)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_divides_by_the_compiles(metric, monkeypatch):
    monkeypatch.setattr(telemetry, "counters",
                        {"hls.compiles": 4, READERS[metric]: 10,
                         "hls.other": 99})
    assert bspec.load_reader(metric)(READINGS) == pytest.approx(2.5)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_nothing_without_a_compile(metric, monkeypatch):
    monkeypatch.setattr(telemetry, "counters", {READERS[metric]: 10})
    assert bspec.load_reader(metric)(READINGS) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_nothing_from_a_program_without_counters(metric,
                                                              monkeypatch):
    import repro.core
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert bspec.load_reader(metric)(READINGS) is None


def test_every_compile_of_the_recompile_cell_counts_alike(monkeypatch):
    """Two compiles with different weights make the same probes and ILP
    cases, so the process-wide mean is each compile's own count."""
    cfg, mod = bspec.load_config("blur_hd")
    dse = cfg["dse"]
    search = hls.SearchConfig(**{k: tuple(v) if isinstance(v, list) else v
                                 for k, v in dse["search"].items()})
    monkeypatch.setattr(telemetry, "counters", {})
    seen = []
    for consts in (mod.consts(cfg), mod.consts(cfg, np.random.default_rng(3))):
        hls.compile(mod.program(cfg, consts, dse=True),
                    objectives=tuple(hls.minimize(o)
                                     for o in dse["objectives"]),
                    search=search)
        seen.append({m: telemetry.counters[c] for m, c in READERS.items()})
    first = seen[0]
    assert all(v > 0 for v in first.values())
    assert seen[1] == {m: 2 * v for m, v in first.items()}
    for m, v in first.items():
        assert bspec.load_reader(m)(READINGS) == v
