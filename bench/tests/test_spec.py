"""BENCHMARK.json resolves to files that exist, by name, and keeps to the
benchmark's naming rules; the harness refuses to run without a TPU."""
import json
import math
import os
import subprocess
import sys

import pytest

from bench import spec as bspec
from repro.core.ir import Program

SPEC = bspec.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_command_and_paths():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_resolves(cfg):
    jpath, ppath = bspec.config_paths(cfg["name"])
    assert cfg["file"] == str(jpath.relative_to(bspec.ROOT))
    data, mod = bspec.load_config(cfg["name"])
    assert data["name"] == cfg["name"]
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    for fn in ("program", "consts", "reference", "counts"):
        assert callable(getattr(mod, fn))
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_has_a_small_size(cfg):
    """Every configuration names ``small``, the sizes the CPU tests drive
    it at (``bench/tests/sizes.py``), and its module builds a program
    there, smaller than the deployment's."""
    data, mod = bspec.load_config(cfg["name"])
    small = data.get("small")
    assert isinstance(small, dict) and small, (
        f"{cfg['file']} has no 'small': the sizes its tests run at")
    assert set(small) <= set(data), (cfg["name"], set(small) - set(data))
    consts = mod.consts(data)
    at_small = mod.program({**data, **small}, consts)
    assert isinstance(at_small, Program), cfg["name"]

    def elements(p):
        return sum(math.prod(a.shape) for a in p.arrays.values())

    assert elements(at_small) < elements(mod.program(data, consts))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    mix = bspec.load_traffic(cell["traffic"])
    assert mix["kind"] in ("stream", "recompile")
    assert cell["chips"] in (1, 4)
    e2e = {m["name"] for m in bspec.end_to_end_of(SPEC, cell["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = bspec.per_layer_of(SPEC, cell["name"])
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        assert callable(bspec.load_reader(m["name"]))


def test_metric_workloads_name_cells():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]


def test_names_units_and_lines():
    names = [c["name"] for c in SPEC["configs"]]
    names += CELLS + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["traffic"] for w in SPEC["workloads"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    for n in names:
        assert bspec.NAME_RE.fullmatch(n), n
    assert len(set(c["name"] for c in SPEC["configs"])) == len(SPEC["configs"])
    assert len(set(CELLS)) == len(CELLS)
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert bspec.UNIT_RE.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for text in ([w["why"] for w in SPEC["workloads"]]
                 + [c["why"] for c in SPEC["configs"]]
                 + [c["source"] for c in SPEC["configs"]]
                 + [m["layer"] for m in SPEC["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_run_exits_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(bspec.BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", str(2**40 + 3), "--seconds", "1", "--trace", "0"],
        cwd=bspec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == ""
