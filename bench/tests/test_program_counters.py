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
SPEC = bspec.load_spec()
RECOMPILED = sorted({w["config"] for w in SPEC["workloads"]
                     if bspec.load_traffic(w["traffic"])["kind"]
                     == "recompile"})
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


@pytest.mark.parametrize("config", RECOMPILED)
def test_every_compile_of_the_recompile_cell_counts_alike(config,
                                                          monkeypatch):
    """For each configuration with a recompile cell: two compiles with
    different weights make the same probes and ILP cases, so the
    process-wide mean is each compile's own count.  The counters are live:
    the II search probes, and the dependence cases are counted, by the
    closed form or by the ILP fallback, whichever takes them."""
    cfg, mod = bspec.load_config(config)
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
        seen.append(dict(telemetry.counters))
    first = seen[0]
    assert first.get("hls.ii_probes", 0) > 0
    assert (first.get("hls.dep_cases_closed", 0)
            + first.get("hls.dep_cases_ilp", 0)) > 0
    for m, c in READERS.items():
        assert seen[1].get(c, 0) == 2 * first.get(c, 0)
        assert bspec.load_reader(m)(READINGS) == first.get(c, 0)
