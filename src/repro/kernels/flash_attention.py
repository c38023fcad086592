"""Flash attention (forward) as a Pallas TPU kernel.

Online-softmax tiling: the grid walks (batch*heads, q blocks); each program
streams kv blocks through VMEM, keeping the running max/denominator in
registers.  Block sizes are MXU-aligned (multiples of 128 on the lane dim).

TPU adaptation notes (DESIGN.md §3): HBM->VMEM streaming replaces the GPU
SRAM tiling of the original flash-attention; the (BQ, BK) score tile feeds
the 128x128 MXU directly; fp32 accumulation in VREGs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, block_k, seq_k):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale          # (BQ, hd)
    BQ, hd = q.shape
    acc = jnp.zeros((BQ, hd), jnp.float32)
    m = jnp.full((BQ,), NEG_INF, jnp.float32)
    l = jnp.zeros((BQ,), jnp.float32)
    nkv = seq_k // block_k

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = q @ k.astype(jnp.float32).T                  # (BQ, BK)
        if causal:
            qpos = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (BQ, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m1 = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m1[:, None])
        alpha = jnp.exp(m - m1)
        l1 = l * alpha + p.sum(axis=1)
        acc1 = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return acc1, m1, l1

    if causal:
        # only kv blocks at or before this q block contribute
        nkv_eff = qi + 1 if isinstance(qi, int) else None
        acc, m, l = jax.lax.fori_loop(
            0, (qi * q_ref.shape[0]) // block_k + 1, body, (acc, m, l))
    else:
        acc, m, l = jax.lax.fori_loop(0, nkv, body, (acc, m, l))
    o_ref[...] = (acc / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    interpret=False):
    """q,k,v: (B, H, S, hd) -> (B, H, S, hd)."""
    B, H, S, hd = q.shape
    Sk = k.shape[2]
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    assert S % block_q == 0 and Sk % block_k == 0
    scale = hd ** -0.5
    qr = q.reshape(B * H, S, hd)
    kr = k.reshape(B * H, Sk, hd)
    vr = v.reshape(B * H, Sk, hd)
    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=Sk),
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, S, hd)
