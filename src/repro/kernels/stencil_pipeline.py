"""Fused producer-consumer stencil chain as a Pallas TPU kernel — the
paper's Fig. 1 pattern (two chained convolutions) adapted to the TPU memory
hierarchy.

The FPGA version overlaps the two loop nests with an ILP-derived slack: the
consumer may start once the producer has written ``halo`` rows.  On TPU the
same slack *sizes the VMEM line buffer*: each grid step loads a row tile plus
``halo`` extra rows, computes the producer stage (conv-x) for the whole tile
in VMEM, and immediately consumes it (conv-y) — the intermediate array never
touches HBM.

Since the codegen backend landed (DESIGN.md §10) this hand-written kernel is
the *golden reference*: ``repro.core.codegen.lower_program`` generates the
same kernel from the ``programs.blur_chain`` IR (the golden test asserts
bit-exact agreement), and the block/halo configuration is read off the
generated kernel — ``hls.compile`` shift-and-peel-fuses the mismatched-bounds
chain, the knee point of the latency x BRAM Pareto frontier is lowered with
``CompileResult.emit_pallas()``, and the kernel's ``block_rows`` / ``halo``
supply both values (the fusion's row shift IS the halo).

This module owns the single implementation; ``repro.kernels.ops`` re-exports
it.  ``interpret`` defaults to compiled: a call on a host with no TPU fails
unless it asks for Pallas interpret mode explicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(img_ref, wx_ref, wy_ref, o_ref, *, block_rows, halo):
    i = pl.program_id(0)
    BR = block_rows
    Wout = o_ref.shape[1]
    # line buffer: BR + halo input rows (the ILP slack), full width
    rows = img_ref[pl.ds(i * BR, BR + halo), :]
    rows = rows.astype(jnp.float32)
    # producer stage: conv-x (3 taps along width)
    wx = wx_ref[...].astype(jnp.float32)
    bx = (rows[:, 0:Wout] * wx[0] + rows[:, 1:Wout + 1] * wx[1]
          + rows[:, 2:Wout + 2] * wx[2])                 # (BR+halo, Wout)
    # consumer stage: conv-y (3 taps along rows) — starts "halo" rows behind
    wy = wy_ref[...].astype(jnp.float32)
    out = bx[0:BR] * wy[0] + bx[1:BR + 1] * wy[1] + bx[2:BR + 2] * wy[2]
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "halo", "interpret"))
def _stencil_call(img, wx, wy, *, block_rows, halo, interpret):
    H, W = img.shape
    Hout, Wout = H - 2, W - 2
    return pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows, halo=halo),
        grid=(Hout // block_rows,),
        in_specs=[
            pl.BlockSpec((H, W), lambda i: (0, 0)),   # streamed line window
            pl.BlockSpec((3,), lambda i: (0,)),
            pl.BlockSpec((3,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, Wout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Hout, Wout), img.dtype),
        interpret=interpret,
    )(img, wx, wy)


def stencil_pipeline(img, wx, wy, *, block_rows=None, halo=None,
                     interpret=False):
    """img: (H, W); wx, wy: (3,).  Returns conv_y(conv_x(img)) of shape
    (H-2, W-2), computed in one fused pass.  ``block_rows``/``halo`` default
    to the DSE-derived configuration (``_stencil_codegen_config``)."""
    if block_rows is None or halo is None:
        dse_rows, dse_halo = _stencil_codegen_config()
        block_rows = dse_rows if block_rows is None else block_rows
        halo = dse_halo if halo is None else halo
    H, _ = img.shape
    Hout = H - 2
    block_rows = min(block_rows, Hout)
    assert Hout % block_rows == 0, (Hout, block_rows)
    return _stencil_call(img, wx, wy, block_rows=block_rows, halo=halo,
                         interpret=interpret)


@functools.lru_cache()
def _stencil_codegen_config(taps: int = 3, n: int = 8) -> tuple[int, int]:
    """(block_rows, halo) for ``stencil_pipeline``, read off the generated
    kernel of the DSE knee point.

    ``hls.compile`` explores transform pipelines over the mismatched-bounds
    blur chain; the knee of the latency x BRAM curve among the candidates
    that shift-and-peel fused the intermediate ``bx`` is lowered with
    ``CompileResult.emit_pallas()`` and the kernel reports its own config:
    ``PallasKernel.halo["bx"]`` is the fusion's row shift (the number of
    producer rows the consumer trails by — the line-buffer halo) and
    ``PallasKernel.block_rows`` the knee's tiling of the fused row loop.
    Raises ``RuntimeError`` when no frontier point shift-fused ``bx`` or the
    knee does not lower.

    Persistence rides the compile cache: ``hls.compile`` stores the whole
    frontier content-addressed (``repro.core.cache``), so a serving process
    pays the sweep once per machine and this function only replays a cache
    hit.  The ``lru_cache`` on top memoizes the in-process lookups; cache
    entries carry the scheduler salt, so a compiler change invalidates them
    and the sweep reruns."""
    from repro.core import hls
    from repro.core.errors import UnlowerableProgram
    from repro.core.programs import blur_chain

    # bram storage so the tile-window footprint term differentiates block
    # sizes; the partition move is excluded — full partitioning is a knob
    # the kernel's VMEM line buffer cannot express
    p = blur_chain(n, storage="bram", taps=taps)
    r = hls.compile(
        p,
        objectives=(hls.minimize("latency"), hls.minimize("bram")),
        search=hls.SearchConfig(moves=("fuse", "tile"), unroll_factors=(),
                                tile_sizes=(2, 4), max_candidates=8))

    def row_shift(c):
        for entry in getattr(c.program, "_fusion_log", []):
            if "bx" in entry["arrays"] and entry["shift"][0] > 0:
                return entry["shift"][0]
        return None

    fused = [c for c in r.frontier if row_shift(c) is not None]
    if not fused:
        raise RuntimeError("DSE sweep found no shifted fusion of bx on the "
                           "frontier")
    # knee of the latency x BRAM trade-off among the fused frontier points,
    # lowered to the generated kernel: its window analysis turns the fusion's
    # row shift into the line-buffer halo, and the knee's tiling of the
    # fused row loop into the Pallas grid's row-block size
    knee = r.knee("latency", "bram", among=fused)
    try:
        kern = r.emit_pallas(knee)
    except UnlowerableProgram as e:
        raise RuntimeError(f"knee point unlowerable: {e}") from e
    return kern.block_rows, kern.halo["bx"]
