"""``BENCHMARK.json`` and the files it names.

Every part of a cell is found by its name: the configuration in
``bench/configs/<config>.json`` with its program, reference and counts in
``bench/configs/<config>.py``, the traffic mix in
``bench/traffic/<traffic>.json``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  Adding a cell adds files and entries; no
file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC_PATH = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


def load_spec(path: Path = SPEC_PATH) -> dict:
    with open(path) as fh:
        return json.load(fh)


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in spec['workloads']]}")


def _checked(name: str) -> str:
    if not NAME_RE.fullmatch(name):
        raise ValueError(f"not a valid name: {name!r}")
    return name


def _module(path: Path, tag: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "bench_" + tag + "_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_paths(name: str) -> tuple[Path, Path]:
    base = BENCH / "configs" / _checked(name)
    return base.with_suffix(".json"), base.with_suffix(".py")


def load_config(name: str) -> tuple[dict, ModuleType]:
    """The configuration's sizes and its module (program, reference,
    counts)."""
    jpath, ppath = config_paths(name)
    with open(jpath) as fh:
        cfg = json.load(fh)
    return cfg, _module(ppath, "config")


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / (_checked(name) + ".json")


def load_traffic(name: str) -> dict:
    with open(traffic_path(name)) as fh:
        return json.load(fh)


def reader_path(metric: str) -> Path:
    return BENCH / "metrics" / (_checked(metric) + ".py")


def load_reader(metric: str):
    """The ``read(readings) -> float | None`` of a per-layer metric."""
    return _module(reader_path(metric), "metric").read


def _in(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def end_to_end_of(spec: dict, cell_name: str) -> list[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in spec["end_to_end"] if _in(m, cell_name)]


def per_layer_of(spec: dict, cell_name: str) -> list[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_of(spec, cell_name)}
    return [m for m in spec["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
