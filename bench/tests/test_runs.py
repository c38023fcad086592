"""A whole run of each cell at a small size on the CPU (the kernel in
Pallas interpret mode, the look for a chip skipped): a sound run comes out
correct, and the control, the program's own bfloat16 path, does not."""
import pytest

from bench import drive
from bench import spec as bspec
from bench.tests.test_faults import (CELLS, SPEC, STREAM_CELLS, _run,
                                    with_batch)


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name):
    r = _run(cell_name)
    assert r["correct"], r
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    c = r["checks"]["rel_err"]
    assert c["value"] <= c["limit"]
    wanted = {m["name"] for m in bspec.end_to_end_of(SPEC, cell_name)}
    assert set(r["metrics"]) == wanted
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("cell_name", CELLS)
def test_program_bfloat16_control_is_not_correct(cell_name):
    r = _run(cell_name, {"dtype": "bfloat16"})
    assert not r["correct"], r


@pytest.mark.parametrize("cell_name", STREAM_CELLS)
def test_stream_mix_without_batch_runs_one_frame_per_call(cell_name,
                                                         monkeypatch):
    """A stream mix that names no ``batch`` calls the kernel on one frame
    per call, and its run is checked the same way."""
    with_batch(monkeypatch, None)
    batched = {"over_frames": 0}
    over = drive.over_frames

    def counted(*a, **k):
        batched["over_frames"] += 1
        return over(*a, **k)

    monkeypatch.setattr(drive, "over_frames", counted)
    r = _run(cell_name)
    assert r["correct"], r
    assert batched["over_frames"] == 0
    assert r["attempted"] > 0 and r["metrics"]["call_us"]["value"] > 0
