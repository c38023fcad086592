"""conv_pool_hd: its counts and shapes at the deployment, its float64
reference against a direct loop, and its lowering at the CPU test size:
streamed, with lane-axis strided takes and a whole-input edge pad, each
counted by the program and read by the benchmark's readers."""
import numpy as np
import pytest

from bench import peaks, run
from bench import spec as bspec
from bench.tests.sizes import small
from repro.core import hls, telemetry
from repro.core.codegen import _point_block_rows, lower_program

CFG, MOD = bspec.load_config("conv_pool_hd")
READINGS = run.Readings(calls=1, trace=None, spans={}, ops=0, nbytes=0,
                        peak=None)


def test_counts():
    ops, nbytes = MOD.counts(CFG)
    assert (ops, nbytes) == (36_806_400, 10_392_016)
    least, bound = peaks.least_time_s(ops, nbytes, peaks.peak_of("TPU v5 lite"))
    assert bound == "bytes"
    assert least == pytest.approx(12.69e-6, abs=0.01e-6)


def test_program_shapes_at_deployment():
    p = MOD.program(CFG, MOD.consts(CFG))
    assert {a: d.shape for a, d in p.arrays.items()} == {
        "img": (1082, 1922), "conv": (1080, 1920), "pool": (540, 960)}
    assert CFG["dtype"] == "float32" and CFG["storage"] == "bram"
    assert (CFG["dse"]["rows"], CFG["dse"]["cols"]) == (8, 8)


def test_odd_sizes_are_refused():
    with pytest.raises(ValueError, match="even"):
        MOD.program({**CFG, "rows": 7}, MOD.consts(CFG))


def _loop_reference(img, w):
    rows, cols = img.shape[0] - 2, img.shape[1] - 2
    conv = np.zeros((rows, cols))
    for i in range(rows):
        for j in range(cols):
            conv[i, j] = sum(w[u][v] * img[i + u, j + v]
                             for u in range(3) for v in range(3))
    pool = np.zeros((rows // 2, cols // 2))
    for i in range(rows // 2):
        for j in range(cols // 2):
            pool[i, j] = max(conv[2 * i + u, 2 * j + v]
                             for u in range(2) for v in range(2))
    return pool


def _edge_frame(rows, cols):
    """Each pool block's largest blur value at another of its four
    corners, spikes on the frame's last input row and column, and negative
    values, which a pool that kept the wrong phase, dropped the last block
    or started its max at zero would get wrong."""
    img = np.random.default_rng(7).uniform(0.5, 2.0, (rows + 2, cols + 2))
    for i in range(rows // 2):
        for j in range(cols // 2):
            u, v = (i + j) % 2, (i // 2 + j) % 2
            img[2 * i + u + 1, 2 * j + v + 1] += 40.0
    img[-1, :] += 25.0
    img[:, -1] -= 60.0
    return img


@pytest.mark.parametrize("frame", ["uniform", "edges"])
@pytest.mark.parametrize("drawn", [False, True])
def test_reference_matches_a_direct_loop(frame, drawn):
    rows, cols = 6, 10
    img = (_edge_frame(rows, cols) if frame == "edges" else
           np.random.default_rng(3).uniform(0.5, 2.0, (rows + 2, cols + 2)))
    consts = MOD.consts(CFG, np.random.default_rng(5) if drawn else None)
    got = MOD.reference({"img": img}, consts)["pool"]
    assert got.shape == (rows // 2, cols // 2) and got.dtype == np.float64
    np.testing.assert_allclose(got, _loop_reference(img, consts["weights"]),
                               rtol=1e-15, atol=0)


def test_lowers_streamed_at_small_with_strided_takes_and_an_edge_pad(
        monkeypatch):
    """The knee of the configuration's own DSE, lowered at ``small``:
    streamed, four lane-axis takes (one per pool tap), and the input
    edge-padded whole because the last row block runs past the frame; the
    readers give the counters per lowering."""
    cfg = {**CFG, **small("conv_pool_hd")}
    consts = MOD.consts(cfg)
    dse = cfg["dse"]
    knee = hls.compile(
        MOD.program(cfg, consts, dse=True),
        objectives=tuple(hls.minimize(o) for o in dse["objectives"]),
        search=hls.SearchConfig(**{k: tuple(v) if isinstance(v, list) else v
                                   for k, v in dse["search"].items()}),
    ).knee(*dse["knee"])
    monkeypatch.setattr(telemetry, "counters", {})
    p = MOD.program(cfg, consts)
    k = lower_program(p, block_rows=_point_block_rows(knee),
                      dtype=cfg["dtype"])
    assert k.mode == "streamed" and k.grid[0] > 1
    c = telemetry.counters
    assert c["hls.lowers"] == 1
    assert c["hls.lane_takes"] == 4
    rows, cols = p.arrays["img"].shape
    pad = k.grid[0] * k.block_rows * 2 + 2 - rows
    assert pad > 0
    assert c["hls.edge_pad_bytes"] == (rows + pad) * cols * 4
    assert bspec.load_reader("lane_takes")(READINGS) == 4
    assert bspec.load_reader("edge_pad_bytes")(READINGS) == \
        c["hls.edge_pad_bytes"]


LOWERING_READERS = {"lane_takes": "hls.lane_takes",
                    "edge_pad_bytes": "hls.edge_pad_bytes"}


@pytest.mark.parametrize("metric", sorted(LOWERING_READERS))
def test_lowering_reader_divides_by_the_lowerings(metric, monkeypatch):
    monkeypatch.setattr(telemetry, "counters",
                        {"hls.lowers": 4, LOWERING_READERS[metric]: 10,
                         "hls.compiles": 99})
    assert bspec.load_reader(metric)(READINGS) == pytest.approx(2.5)


@pytest.mark.parametrize("metric", sorted(LOWERING_READERS))
def test_lowering_reader_reads_nothing_without_a_lowering(metric,
                                                          monkeypatch):
    """A program that counts no ``hls.lowers``, as one from before the
    counter, reads None and does not raise."""
    monkeypatch.setattr(telemetry, "counters", {LOWERING_READERS[metric]: 10,
                                                "hls.compiles": 1})
    assert bspec.load_reader(metric)(READINGS) is None


@pytest.mark.parametrize("metric", sorted(LOWERING_READERS))
def test_lowering_reader_reads_nothing_from_a_program_without_counters(
        metric, monkeypatch):
    import sys

    import repro.core
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert bspec.load_reader(metric)(READINGS) is None
