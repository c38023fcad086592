"""Compile-only tests: the kernels of the main path compiled for a TPU v5e
that is described, not attached (no chip runs here, so these say nothing
about results or times).

They catch what Pallas interpret mode cannot: blocks not aligned to the
(8, 128) tiling, dynamic row offsets the compiler cannot prove aligned,
strided slices it does not lower, and kernels that overflow VMEM.  The
topology is described inside a fixture, never at import, because only one
process at a time may load the TPU library; keep all such tests in this
one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hls
from repro.core.codegen import SUBLANE_ROWS, _point_block_rows, lower_program
from repro.core.programs import CHAIN_BENCHMARKS, dus, optical_flow, two_mm
from repro.kernels.stencil_pipeline import stencil_pipeline

FRAME_N = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compile_kernel(k, p, sharding):
    shapes = {a: jax.ShapeDtypeStruct(p.arrays[a].shape, jnp.float32,
                                      sharding=sharding) for a in p.arrays}
    _assert_kernel(jax.jit(k.fn).lower(shapes).compile())


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(CHAIN_BENCHMARKS))
def test_streamed_chain_knee_compiles(one_chip, name):
    """The DSE knee of each fusion chain compiles for the chip, both as
    emit_pallas lowers it at the DSE size and lowered at a megapixel frame
    with the block emit_pallas maps its tile to."""
    mk = CHAIN_BENCHMARKS[name]
    r = hls.compile(
        mk(6 if name == "gradient_harris" else 8, storage="bram"),
        objectives=(hls.minimize("latency"), hls.minimize("bram")),
        search=hls.SearchConfig(moves=("fuse", "tile"), unroll_factors=(),
                                tile_sizes=(2, 4), max_candidates=8,
                                cache=False))
    knee = r.knee("latency", "bram")
    _compile_kernel(r.emit_pallas(knee), r.program, one_chip)
    p = mk(FRAME_N, storage="bram")
    k = lower_program(p, block_rows=_point_block_rows(knee))
    assert k.mode == "streamed", k.soft_reasons
    assert k.block_rows % SUBLANE_ROWS == 0
    _compile_kernel(k, p, one_chip)


@pytest.mark.parametrize("mk,n", [(optical_flow, 256), (dus, 512),
                                  (two_mm, 128)],
                         ids=["optical_flow", "dus", "two_mm"])
def test_whole_array_kernel_compiles(one_chip, mk, n):
    """Mode B at the sizes chip_smoke.py runs; dus exercises the strided
    loads (_take) and strided stores (_strided_set), two_mm the unrolled
    reduction fold."""
    p = mk(n, storage="bram")
    k = lower_program(p)
    assert k.mode == "whole", k.soft_reasons
    _compile_kernel(k, p, one_chip)


def test_handwritten_stencil_pipeline_compiles(one_chip):
    img = jax.ShapeDtypeStruct((FRAME_N + 2, FRAME_N + 2), jnp.float32,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((3,), jnp.float32, sharding=one_chip)
    _assert_kernel(jax.jit(lambda x, wx, wy: stencil_pipeline(
        x, wx, wy, block_rows=SUBLANE_ROWS, halo=2)).lower(img, w, w).compile())
