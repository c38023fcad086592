"""Public entry points for the hand-written Pallas kernels + the ILP/kernel
bridge.

Every kernel runs compiled (``interpret=False``) unless the caller asks for
Pallas interpret mode; a compiled call on a host with no TPU fails instead of
quietly interpreting.

``stencil_pipeline`` (and its configuration helpers — the deprecated
``stencil_dse_config`` wrapper and the fallback ``ilp_halo_rows``) are
re-exported from ``repro.kernels.stencil_pipeline`` — that module owns the
single implementation.  The blessed configuration path is
``hls.compile(...).emit_pallas()`` (DESIGN.md §10).
"""
from __future__ import annotations

from repro.kernels.flash_attention import flash_attention
from repro.kernels.stencil_pipeline import (ilp_halo_rows, stencil_dse_config,
                                            stencil_pipeline)
from repro.kernels.wkv6 import wkv6

__all__ = ["flash_attention", "stencil_pipeline", "stencil_dse_config",
           "ilp_halo_rows", "wkv6"]
