"""Public entry points for the hand-written Pallas kernels.

Every kernel runs compiled (``interpret=False``) unless the caller asks for
Pallas interpret mode; a compiled call on a host with no TPU fails instead of
quietly interpreting.

``stencil_pipeline`` is re-exported from ``repro.kernels.stencil_pipeline``,
which owns the single implementation; its block/halo configuration comes
from ``hls.compile(...).emit_pallas()`` (DESIGN.md §10).
"""
from __future__ import annotations

from repro.kernels.flash_attention import flash_attention
from repro.kernels.stencil_pipeline import stencil_pipeline
from repro.kernels.wkv6 import wkv6

__all__ = ["flash_attention", "stencil_pipeline", "wkv6"]
