"""RWKV-6 (Finch) WKV recurrence as a Pallas TPU kernel.

Grid = (batch, heads); each program owns one head's (hd x hd) state matrix in
fp32 and walks the sequence in chunks.  Within a chunk the recurrence is
evaluated in the parallel (linear-attention) form — cumulative log-decays, a
strictly-lower-triangular intra-chunk attention, the diagonal "bonus" u term,
and a carried cross-chunk state — so the MXU sees (C x hd)@(hd x hd) matmuls
instead of a length-S scalar chain.  The published CUDA kernel keeps the
state in shared memory and serializes tokens; the TPU adaptation trades that
for chunked matrix form (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, *, chunk, seq):
    hd = r_ref.shape[-1]
    C = chunk
    u = u_ref[...].astype(jnp.float32)                     # (hd,)
    tri = jnp.tril(jnp.ones((C, C), jnp.float32), -1)

    def body(j, state):
        sl = (pl.ds(j * C, C), slice(None))
        r = r_ref[sl].astype(jnp.float32)                  # (C, hd)
        k = k_ref[sl].astype(jnp.float32)
        v = v_ref[sl].astype(jnp.float32)
        w = w_ref[sl].astype(jnp.float32)
        logw = jnp.log(w)
        cw = jnp.cumsum(logw, axis=0)                      # (C, hd)
        rd = r * jnp.exp(cw - logw)
        kd = k * jnp.exp(-cw)
        att = (rd @ kd.T) * tri                            # (C, C)
        out = att @ v
        # bonus term (current token only): o += (r . (u*k)) v
        bonus = jnp.sum(r * k * u[None, :], axis=1, keepdims=True) * v
        out = out + bonus
        out = out + rd @ state                             # carried state
        wtot = jnp.exp(cw[-1])                             # (hd,)
        state1 = state * wtot[:, None] + \
            (k * jnp.exp(cw[-1][None, :] - cw)).T @ v
        o_ref[sl] = out.astype(o_ref.dtype)
        return state1

    state0 = jnp.zeros((hd, hd), jnp.float32)
    jax.lax.fori_loop(0, seq // C, body, state0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk=64, interpret=False):
    """r,k,v,w: (B, H, S, hd); w is the per-token decay in (0,1);
    u: (H, hd).  Returns (B, H, S, hd)."""
    B, H, S, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    return pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, seq=S),
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((None, None, S, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, S, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, S, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, S, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, hd), lambda b, h: (h, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, S, hd), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), r.dtype),
        interpret=interpret,
    )(r, k, v, w, u)
