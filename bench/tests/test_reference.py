"""The plain references agree with the program's own interpreter, a
bfloat16 computation of the same outputs fails the comparison, and the
algorithm's counts and the peaks are pinned."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, peaks
from bench import spec as bspec
from bench.tests.sizes import small
from repro.core import sim

CONFIGS = [c["name"] for c in bspec.load_spec()["configs"]]
# each configuration with its nominal constants, and with drawn ones where
# it draws them (``draw_weights``), as its recompile steps do
CASES = ([(name, False) for name in CONFIGS]
         + [(name, True) for name in CONFIGS
            if "draw_weights" in bspec.load_config(name)[0]])


def _case(name, drawn=False):
    cfg, mod = bspec.load_config(name)
    cfg = {**cfg, **small(name)}
    consts = mod.consts(cfg, np.random.default_rng(5) if drawn else None)
    p = mod.program(cfg, consts)
    arrays = sim.make_inputs(p, seed=3)
    for a, d in cfg["inputs"].items():
        if d.get("zeros"):
            arrays[a][...] = 0.0
    return cfg, mod, consts, p, arrays


@pytest.mark.parametrize("name,drawn", CASES)
def test_reference_matches_sequential_exec(name, drawn):
    cfg, mod, consts, p, arrays = _case(name, drawn)
    want = sim.sequential_exec(p, arrays)
    got = mod.reference(arrays, consts)
    for out in cfg["outputs"]:
        np.testing.assert_allclose(got[out], want[out], rtol=1e-12, atol=0)


@pytest.mark.parametrize("name", CONFIGS)
def test_bfloat16_fails_the_comparison(name):
    cfg, mod, consts, p, arrays = _case(name)
    f32 = {a: v.astype(np.float32) for a, v in arrays.items()}
    ref = mod.reference(f32, consts)
    low = mod.reference(f32, consts, xp=jnp, dtype=jnp.bfloat16)
    assert compare.rel_err(low, ref, cfg["outputs"]) > cfg["limits"]["rel_err"]
    # and float32 on the same inputs passes
    mid = mod.reference(f32, consts, xp=np, dtype=np.float32)
    assert compare.rel_err(mid, ref, cfg["outputs"]) <= cfg["limits"]["rel_err"]


def test_rel_err_flags_missing_and_nonfinite():
    ref = {"x": np.ones((2, 2))}
    assert compare.rel_err({}, ref, ["x"]) == math.inf
    assert compare.rel_err({"x": np.ones((2, 3))}, ref, ["x"]) == math.inf
    bad = np.ones((2, 2))
    bad[0, 0] = np.nan
    assert compare.rel_err({"x": bad}, ref, ["x"]) == math.inf
    assert compare.rel_err({"x": np.full((2, 2), 1.5)}, ref, ["x"]) == 0.5


@pytest.mark.parametrize("name,ops,nbytes", [
    ("blur_hd", 20_755_200, 16_612_816),
    ("two_mm_medium", 29_412_000, 1_068_400)])
def test_counts(name, ops, nbytes):
    cfg, mod = bspec.load_config(name)
    assert mod.counts(cfg) == (ops, nbytes)
    least, bound = peaks.least_time_s(ops, nbytes, peaks.peak_of("TPU v5 lite"))
    assert bound == "bytes"
    assert least == nbytes / 819e9


def test_program_shapes_at_deployment():
    cfg, mod = bspec.load_config("blur_hd")
    p = mod.program(cfg, mod.consts(cfg))
    assert p.arrays["img"].shape == (1082, 1922)
    assert p.arrays["by"].shape == (1080, 1920)
    cfg, mod = bspec.load_config("two_mm_medium")
    p = mod.program(cfg, mod.consts(cfg))
    assert {a: d.shape for a, d in p.arrays.items()} == {
        "A": (180, 210), "B": (210, 190), "C": (190, 220),
        "tmp": (180, 190), "D": (180, 220)}


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_of("TPU v9 imaginary")
