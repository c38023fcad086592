"""A whole run of each cell, at a small size on the CPU (the kernel in
Pallas interpret mode, the look for a chip skipped): a sound run comes out
correct, and a run with the timed path broken underneath, or computed in
the program's own bfloat16 path (the control), comes out not correct."""
import dataclasses

import jax.numpy as jnp
import pytest

from bench import drive, run
from bench import spec as bspec
from bench.tests.sizes import small

SPEC = bspec.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**35 + 11


def _altered(fn):
    """One answer altered where it is produced."""
    def run_(arrays, interpret=False):
        out = dict(fn(arrays, interpret=interpret))
        name = sorted(out)[0]
        out[name] = out[name].at[0, 0].add(0.25)
        return out
    return run_


def _unchanged(fn):
    """A step that returns its state unchanged: each output as it came in."""
    def run_(arrays, interpret=False):
        out = fn(arrays, interpret=interpret)
        return {name: jnp.asarray(arrays[name], v.dtype)
                for name, v in out.items()}
    return run_


def _half(fn):
    """Half of the batch left out: the rows of the second half never
    computed."""
    def run_(arrays, interpret=False):
        out = fn(arrays, interpret=interpret)
        return {name: v.at[v.shape[0] // 2:].set(0) for name, v in out.items()}
    return run_


FAULTS = {"altered": _altered, "unchanged": _unchanged, "half": _half}


def _run(cell_name, override=None, seconds=0.2):
    cell = bspec.cell(SPEC, cell_name)
    cfg = {**small(cell["config"]), **(override or {})}
    result, _ = run.run_cell(SPEC, cell, SEED, seconds, False, t0=0.0,
                             cfg_override=cfg, interpret=True)
    return result


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell_name", CELLS)
def test_fault_is_not_correct(cell_name, fault, monkeypatch):
    lower = drive.lower_program

    def broken(*a, **k):
        kern = lower(*a, **k)
        return dataclasses.replace(kern, fn=FAULTS[fault](kern.fn))

    monkeypatch.setattr(drive, "lower_program", broken)
    r = _run(cell_name)
    assert not r["correct"], r
    assert r["checks"]["rel_err"]["value"] > r["checks"]["rel_err"]["limit"]


STREAM_CELLS = [c for c in CELLS
                if bspec.load_traffic(bspec.cell(SPEC, c)["traffic"])["kind"]
                == "stream"]


def with_batch(monkeypatch, batch):
    """The cells' traffic mixes with ``batch`` frames to a call (``None``:
    one frame, no batch), whatever their files say."""
    load = bspec.load_traffic

    def mix(name):
        m = {k: v for k, v in load(name).items() if k != "batch"}
        return m if batch is None else {**m, "batch": batch}

    monkeypatch.setattr(bspec, "load_traffic", mix)


@pytest.mark.parametrize("cell_name", STREAM_CELLS)
def test_half_of_the_frames_left_out_is_not_correct(cell_name, monkeypatch):
    """Half of a call's batch of frames never computed: the kernel runs
    over the first half, whose answers stand in for the rest."""
    with_batch(monkeypatch, 4)
    over = drive.over_frames

    def broken(fn, mapped):
        run_ = over(fn, mapped)

        def half(arrays):
            n = arrays[mapped[0]].shape[0]
            first = run_({k: (v[:n // 2] if k in mapped else v)
                          for k, v in arrays.items()})
            return {k: jnp.concatenate([v, v[:n - n // 2]])
                    for k, v in first.items()}
        return half

    monkeypatch.setattr(drive, "over_frames", broken)
    r = _run(cell_name)
    assert not r["correct"], r
    assert r["checks"]["rel_err"]["value"] > r["checks"]["rel_err"]["limit"]
