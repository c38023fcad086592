"""Shared model layers (pure functions over pytrees of jnp arrays).

Everything is written to be `lax.scan`-stackable over layers so the HLO
stays compact.  The layers are single-device: they are kept as the reference
definitions for compiling traced layers through ``frontend.trace``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ArchConfig


def _dt(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# normalization + rotary
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta):
    """x: (..., S, H, hd); positions: (..., S)"""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense attention (GQA / MQA) with optional KV cache
# ---------------------------------------------------------------------------


def init_attn(cfg: ArchConfig, key):
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = D ** -0.5
    dt = _dt(cfg)
    return {
        "wq": (jax.random.normal(k1, (D, H, hd)) * s).astype(dt),
        "wk": (jax.random.normal(k2, (D, K, hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (D, K, hd)) * s).astype(dt),
        "wo": (jax.random.normal(k4, (H, hd, D)) * s).astype(dt),
        "norm": jnp.ones((D,), dt),
    }


def _repeat_kv(k, n_rep):
    """(B,T,K,hd) -> (B,T,K*n_rep,hd).  Materializing the repeat keeps the
    attention einsums 4-D with a single head axis, which XLA's SPMD
    propagation shards cleanly over "model" (the 5-D grouped form was
    replicated across the model axis — a 3x compute bug found in the
    dry-run roofline; see EXPERIMENTS.md §Perf)."""
    if n_rep == 1:
        return k
    B, T, K, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, T, K, n_rep, hd)) \
        .reshape(B, T, K * n_rep, hd)


def _sdpa(cfg, q, k, v, mask, dtype):
    """q: (B,S,H,hd); k,v: (B,T,H,hd); mask broadcastable to (B,H,S,T).

    cfg.scores_bf16 keeps the (S x T) score tensor in bf16 with fp32 row
    statistics (flash-attention numerics) — halves the dominant attention
    traffic on memory-bound train/prefill cells (§Perf)."""
    hd = q.shape[-1]
    sd = jnp.bfloat16 if cfg.scores_bf16 else jnp.float32
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(sd) * \
        jnp.asarray(hd ** -0.5, sd)
    neg = jnp.asarray(jnp.finfo(sd).min / 2, sd)
    scores = jnp.where(mask, scores, neg)
    m = jax.lax.stop_gradient(scores.max(axis=-1, keepdims=True))
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True, dtype=jnp.float32)
    w = (p / l.astype(sd)).astype(dtype)
    return jnp.einsum("bhst,bthd->bshd", w, v)


def _sdpa_chunked(cfg, q, k, v, pos_q, pos_k, dtype):
    """Flash-style chunked attention in pure JAX (hillclimb lever for the
    memory-bound train/prefill cells): lax.scan over kv blocks with online
    softmax — the (S x T) score matrix never materializes at once; the mask
    is an iota comparison per block instead of a (B,1,S,T) bool tensor.
    q: (B,S,H,hd); k,v: (B,T,H,hd); pos_*: (B,S)/(B,T)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    C = min(cfg.attn_chunk, T)
    assert T % C == 0, (T, C)
    q = q.astype(jnp.float32)
    scale = hd ** -0.5
    kc = k.reshape(B, T // C, C, H, hd).swapaxes(0, 1)
    vc = v.reshape(B, T // C, C, H, hd).swapaxes(0, 1)
    pc = pos_k.reshape(B, T // C, C).swapaxes(0, 1)

    def body(carry, blk):
        acc, m, l = carry
        kb, vb, pb = blk
        s = jnp.einsum("bshd,bchd->bhsc", q, kb.astype(jnp.float32)) * scale
        valid = pos_q[:, None, :, None] >= pb[:, None, None, :]
        s = jnp.where(valid, s, -1e30)
        m1 = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m1[..., None])
        alpha = jnp.exp(m - m1)
        l1 = l * alpha + p.sum(axis=-1)
        acc1 = acc * alpha[..., None] + jnp.einsum(
            "bhsc,bchd->bhsd", p, vb.astype(jnp.float32))
        return (acc1, m1, l1), None

    acc0 = jnp.zeros((B, H, S, hd), jnp.float32)
    m0 = jnp.full((B, H, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kc, vc, pc))
    return (acc / (l[..., None] + 1e-30)).swapaxes(1, 2).astype(dtype)


def attn_forward(cfg: ArchConfig, p, x, positions, causal=True):
    """Full-sequence attention (train / prefill). x: (B, S, D)."""
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    kr, vr = _repeat_kv(k, H // K), _repeat_kv(v, H // K)
    if cfg.attn_impl == "chunked" and causal:
        o = _sdpa_chunked(cfg, q, kr, vr, positions, positions, x.dtype)
    else:
        if causal:
            mask = (positions[:, :, None] >= positions[:, None, :])[:, None]
        else:
            mask = jnp.ones((B, 1, S, S), bool)
        o = _sdpa(cfg, q, kr, vr, mask, x.dtype)
    return x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def attn_decode(cfg: ArchConfig, p, x, cache, pos):
    """One-token decode. x: (B, 1, D); cache: {k,v: (B, Smax, K, hd)};
    pos: (B,) current write position."""
    B = x.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)
    ck = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u, (i, 0, 0)))(cache["k"], k[:, 0:1], pos)
    cv = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u, (i, 0, 0)))(cache["v"], v[:, 0:1], pos)
    Smax = ck.shape[1]
    valid = (jnp.arange(Smax)[None, :] <= pos[:, None])[:, None, None, :]
    o = _sdpa(cfg, q, _repeat_kv(ck, H // K), _repeat_kv(cv, H // K), valid,
              x.dtype)
    out = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, {"k": ck, "v": cv}


def init_attn_cache(cfg: ArchConfig, B, Smax, dt):
    K, hd = cfg.n_kv_heads, cfg.hd
    return {"k": jnp.zeros((B, Smax, K, hd), dt),
            "v": jnp.zeros((B, Smax, K, hd), dt)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(cfg: ArchConfig, key):
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    dt = _dt(cfg)
    s = D ** -0.5
    qd = m.nope_head_dim + m.rope_head_dim
    return {
        "wdq": (jax.random.normal(ks[0], (D, m.q_lora_rank)) * s).astype(dt),
        "wuq": (jax.random.normal(ks[1], (m.q_lora_rank, H, qd))
                * m.q_lora_rank ** -0.5).astype(dt),
        "wdkv": (jax.random.normal(ks[2], (D, m.kv_lora_rank + m.rope_head_dim))
                 * s).astype(dt),
        "wukv": (jax.random.normal(
            ks[3], (m.kv_lora_rank, H, m.nope_head_dim + m.v_head_dim))
            * m.kv_lora_rank ** -0.5).astype(dt),
        "wo": (jax.random.normal(ks[4], (H, m.v_head_dim, D)) * s).astype(dt),
        "norm": jnp.ones((D,), dt),
    }


def _mla_qkv(cfg, p, h, positions):
    m = cfg.mla
    H = cfg.n_heads
    q = jnp.einsum("bsd,dr->bsr", h, p["wdq"])
    q = jnp.einsum("bsr,rhq->bshq", q, p["wuq"])
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv = jnp.einsum("bsd,dr->bsr", h, p["wdkv"])
    c_kv, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def _mla_attend(cfg, p, x, q_nope, q_rope, c_kv, k_rope, valid):
    """c_kv: (B, T, r); k_rope: (B, T, rope_hd) shared across heads."""
    m = cfg.mla
    B, S = q_nope.shape[:2]
    kv = jnp.einsum("btr,rhe->bthe", c_kv, p["wukv"])
    k_nope, v = jnp.split(kv, [m.nope_head_dim], axis=-1)
    sc = jnp.einsum("bshq,bthq->bhst", q_nope, k_nope)
    sc = sc + jnp.einsum("bshq,btq->bhst", q_rope, k_rope)
    sc = sc.astype(jnp.float32) * ((m.nope_head_dim + m.rope_head_dim) ** -0.5)
    sc = jnp.where(valid, sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhst,bthv->bshv", w, v)
    return x + jnp.einsum("bshv,hvd->bsd", o, p["wo"])


def mla_forward(cfg: ArchConfig, p, x, positions, causal=True):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, p, h, positions)
    valid = (positions[:, None, :, None] >= positions[:, None, None, :]) \
        if causal else True
    return _mla_attend(cfg, p, x, q_nope, q_rope, c_kv, k_rope, valid)


def mla_decode(cfg: ArchConfig, p, x, cache, pos):
    """Cache stores the COMPRESSED latents (B, Smax, r + rope_hd) — the whole
    point of MLA: the per-token cache is kv_lora + rope wide, not 2*H*hd."""
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(cfg, p, h, pos[:, None])
    upd = jnp.concatenate([c_kv_new, k_rope_new], axis=-1)
    ck = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u, (i, 0)))(cache["ckv"], upd, pos)
    m = cfg.mla
    c_kv, k_rope = jnp.split(ck, [m.kv_lora_rank], axis=-1)
    Smax = ck.shape[1]
    valid = (jnp.arange(Smax)[None, :] <= pos[:, None])[:, None, None, :]
    out = _mla_attend(cfg, p, x, q_nope, q_rope, c_kv, k_rope, valid)
    return out, {"ckv": ck}


def init_mla_cache(cfg: ArchConfig, B, Smax, dt):
    m = cfg.mla
    return {"ckv": jnp.zeros((B, Smax, m.kv_lora_rank + m.rope_head_dim), dt)}


# ---------------------------------------------------------------------------
# FFN: swiglu / geglu / gelu  + MoE
# ---------------------------------------------------------------------------


def init_mlp(cfg: ArchConfig, key, d_ff=None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = _dt(cfg)
    p = {"norm": jnp.ones((D,), dt),
         "w_up": (jax.random.normal(k2, (D, F)) * D ** -0.5).astype(dt),
         "w_down": (jax.random.normal(k3, (F, D)) * F ** -0.5).astype(dt)}
    if cfg.act in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(k1, (D, F)) * D ** -0.5).astype(dt)
    return p


def mlp_forward(cfg: ArchConfig, p, x):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    up = h @ p["w_up"]
    if cfg.act == "swiglu":
        up = jax.nn.silu(h @ p["w_gate"]) * up
    elif cfg.act == "geglu":
        up = jax.nn.gelu(h @ p["w_gate"]) * up
    else:  # gelu (whisper-style 2-matrix MLP)
        up = jax.nn.gelu(up)
    return x + up @ p["w_down"]


def init_moe(cfg: ArchConfig, key):
    D = cfg.d_model
    mc = cfg.moe
    E, F = mc.n_experts, mc.d_ff
    ks = jax.random.split(key, 5)
    dt = _dt(cfg)
    p = {
        "norm": jnp.ones((D,), dt),
        "router": (jax.random.normal(ks[0], (D, E)) * D ** -0.5).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, D, F)) * D ** -0.5).astype(dt),
        "w_up": (jax.random.normal(ks[2], (E, D, F)) * D ** -0.5).astype(dt),
        "w_down": (jax.random.normal(ks[3], (E, F, D)) * F ** -0.5).astype(dt),
    }
    if mc.n_shared:
        p["shared"] = init_mlp(cfg, ks[4], d_ff=mc.d_ff * mc.n_shared)
    return p


def moe_forward(cfg: ArchConfig, p, x):
    """Grouped capacity-based top-k MoE with gather/scatter dispatch.

    The textbook one-hot *einsum* dispatch costs O(T * E * C * D) dense FLOPs
    — at DeepSeek/Kimi scale that dwarfs the experts themselves (observed
    175x overcount in the dry-run roofline).  Instead we scatter token ids
    into (E, C) slot tables and gather activations, so dispatch costs bytes,
    not FLOPs.  Groups = batch rows."""
    B, S, D = x.shape
    mc = cfg.moe
    E, K = mc.n_experts, mc.top_k
    h = rms_norm(x, p["norm"], cfg.norm_eps)          # (G, Tg, D); G=B, Tg=S
    G, Tg = B, S
    logits = h.astype(jnp.float32) @ p["router"]      # (G, Tg, E)
    gates = jax.nn.softmax(logits, axis=-1)
    gval, gidx = jax.lax.top_k(gates, K)              # (G, Tg, K)
    gval = gval / (jnp.sum(gval, axis=-1, keepdims=True) + 1e-9)
    C = max(1, int(Tg * K * mc.capacity_factor / E))
    # position-in-expert WITHOUT the (T, K, E) one-hot cumsum (which costs
    # O(T*K*E) memory — 13 TB at Kimi scale, the dominant traffic in the
    # baseline roofline): sort the flat expert ids, rank within runs, and
    # scatter the ranks back.
    N = Tg * K
    eflat = gidx.reshape(G, N)
    order = jnp.argsort(eflat, axis=1, stable=True)           # (G, N)
    sorted_e = jnp.take_along_axis(eflat, order, axis=1)
    first = jax.vmap(lambda s: jnp.searchsorted(s, s, side="left"))(sorted_e)
    ranks = jnp.arange(N)[None, :] - first                    # pos in expert
    gdx0 = jnp.arange(G)[:, None]
    posc = jnp.zeros((G, N), jnp.int32).at[gdx0, order].set(
        ranks.astype(jnp.int32)).reshape(G, Tg, K)
    keep = posc < C                                    # (G, Tg, K)
    slot = gidx * C + posc                             # unique per kept (t,k)
    flat_slot = jnp.where(keep, slot, E * C)           # overflow bucket
    gdx = jnp.arange(G)[:, None, None]
    tok = jnp.broadcast_to(jnp.arange(Tg)[None, :, None], (G, Tg, K))
    src = jnp.zeros((G, E * C + 1), jnp.int32).at[gdx, flat_slot].set(
        tok, mode="drop")[:, :E * C]
    vld = jnp.zeros((G, E * C + 1), x.dtype).at[gdx, flat_slot].set(
        jnp.ones((G, Tg, K), x.dtype), mode="drop")[:, :E * C]
    # dispatch: gather tokens into (G, E, C, D) expert buffers
    xin = jnp.take_along_axis(h, src[..., None], axis=1) * vld[..., None]
    xin = xin.reshape(G, E, C, D)
    act = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, p["w_gate"]))
    mid = act * jnp.einsum("gecd,edf->gecf", xin, p["w_up"])
    xout = jnp.einsum("gecf,efd->gecd", mid, p["w_down"])
    # combine: gather each (t, k)'s slot back and weight by the gate
    flat = xout.reshape(G, E * C, D)
    vals = jnp.take_along_axis(
        flat, jnp.clip(slot, 0, E * C - 1).reshape(G, Tg * K)[..., None],
        axis=1).reshape(G, Tg, K, D)
    w = (gval.astype(x.dtype) * keep.astype(x.dtype))[..., None]
    out = jnp.sum(vals * w, axis=2)                    # (G, Tg, D)
    if mc.n_shared:
        return mlp_forward(cfg, p["shared"], x) + out
    return x + out


# ---------------------------------------------------------------------------
# Mamba (selective SSM) — chunked scan, O(S) memory in the chunk size
# ---------------------------------------------------------------------------


def init_mamba(cfg: ArchConfig, key):
    D = cfg.d_model
    di = cfg.mamba_expand * D
    N = cfg.mamba_d_state
    kc = cfg.mamba_d_conv
    dt_rank = max(1, D // 16)
    ks = jax.random.split(key, 7)
    dt = _dt(cfg)
    return {
        "norm": jnp.ones((D,), dt),
        "w_in": (jax.random.normal(ks[0], (D, 2 * di)) * D ** -0.5).astype(dt),
        "conv_w": (jax.random.normal(ks[1], (kc, di)) * kc ** -0.5).astype(dt),
        "w_bc": (jax.random.normal(ks[2], (di, 2 * N)) * di ** -0.5).astype(dt),
        "w_dt": (jax.random.normal(ks[3], (di, dt_rank)) * di ** -0.5).astype(dt),
        "w_dt2": (jax.random.normal(ks[4], (dt_rank, di)) * dt_rank ** -0.5).astype(dt),
        "a_log": jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32), (di, 1))),
        "d_skip": jnp.ones((di,), jnp.float32),
        "w_out": (jax.random.normal(ks[5], (di, D)) * di ** -0.5).astype(dt),
    }


def _mamba_core(cfg, p, xz, h0, conv_tail):
    """xz: (B, S, 2*di). Returns (y, h_final, new_conv_tail)."""
    B, S, _ = xz.shape
    di = cfg.mamba_expand * cfg.d_model
    N = cfg.mamba_d_state
    kc = cfg.mamba_d_conv
    x, z = jnp.split(xz, 2, axis=-1)
    # causal short conv along S (tail carries state across calls)
    xp = jnp.concatenate([conv_tail, x], axis=1)
    c = sum(xp[:, i:i + S, :] * p["conv_w"][i] for i in range(kc))
    new_tail = xp[:, S:S + kc - 1, :]
    c = jax.nn.silu(c)
    bc = jnp.einsum("bsd,dn->bsn", c, p["w_bc"])
    Bm, Cm = jnp.split(bc, 2, axis=-1)                       # (B,S,N)
    dt_ = jax.nn.softplus(
        jnp.einsum("bsd,dr,re->bse", c, p["w_dt"], p["w_dt2"]).astype(jnp.float32))
    A = -jnp.exp(p["a_log"])                                  # (di, N)
    decay = jnp.exp(dt_[..., None] * A)                       # (B,S,di,N)
    drive = (dt_ * c.astype(jnp.float32))[..., None] * Bm[:, :, None, :]

    def assoc(a, b):
        return (a[0] * b[0], a[1] * b[0] + b[1])

    dec_c, drv_c = jax.lax.associative_scan(assoc, (decay, drive), axis=1)
    # fold in the carried state h0: h_t = dec_c * h0 + drv_c
    h = dec_c * h0[:, None] + drv_c                           # (B,S,di,N)
    y = jnp.einsum("bsdn,bsn->bsd", h, Cm.astype(jnp.float32))
    y = y + p["d_skip"] * c.astype(jnp.float32)
    y = y.astype(xz.dtype) * jax.nn.silu(z)
    return y, h[:, -1], new_tail


def mamba_forward(cfg: ArchConfig, p, x, chunk=256):
    B, S, D = x.shape
    di = cfg.mamba_expand * D
    N = cfg.mamba_d_state
    kc = cfg.mamba_d_conv
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    xz = jnp.einsum("bsd,de->bse", h, p["w_in"])
    chunk = min(chunk, S)
    assert S % chunk == 0, "seq_len must be divisible by the mamba chunk"
    xz_c = xz.reshape(B, S // chunk, chunk, 2 * di).swapaxes(0, 1)

    def step(carry, xc):
        h0, tail = carry
        y, h1, tail1 = _mamba_core(cfg, p, xc, h0, tail)
        return (h1, tail1), y

    h0 = jnp.zeros((B, di, N), jnp.float32)
    tail0 = jnp.zeros((B, kc - 1, di), xz.dtype)
    _, ys = jax.lax.scan(step, (h0, tail0), xz_c)
    y = ys.swapaxes(0, 1).reshape(B, S, di)
    return x + jnp.einsum("bse,ed->bsd", y, p["w_out"])


def mamba_decode(cfg: ArchConfig, p, x, cache):
    """One-token decode; cache = {h: (B,di,N) fp32, tail: (B,kc-1,di)}."""
    y, h1, tail1 = _mamba_core(
        cfg, p,
        jnp.einsum("bsd,de->bse", rms_norm(x, p["norm"], cfg.norm_eps), p["w_in"]),
        cache["h"], cache["tail"])
    out = x + jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return out, {"h": h1, "tail": tail1}


def init_mamba_cache(cfg: ArchConfig, B, dt):
    di = cfg.mamba_expand * cfg.d_model
    return {"h": jnp.zeros((B, di, cfg.mamba_d_state), jnp.float32),
            "tail": jnp.zeros((B, cfg.mamba_d_conv - 1, di), dt)}


# ---------------------------------------------------------------------------
# RWKV-6 (Finch): token shift + data-dependent decay WKV
# ---------------------------------------------------------------------------


def init_rwkv(cfg: ArchConfig, key):
    D = cfg.d_model
    ks = jax.random.split(key, 8)
    dt = _dt(cfg)
    s = D ** -0.5
    return {
        "norm_a": jnp.ones((D,), dt),
        "norm_f": jnp.ones((D,), dt),
        "mix": (jax.random.normal(ks[0], (5, D)) * 0.01).astype(dt),
        "wr": (jax.random.normal(ks[1], (D, D)) * s).astype(dt),
        "wk": (jax.random.normal(ks[2], (D, D)) * s).astype(dt),
        "wv": (jax.random.normal(ks[3], (D, D)) * s).astype(dt),
        "wg": (jax.random.normal(ks[4], (D, D)) * s).astype(dt),
        "wdecay": (jax.random.normal(ks[5], (D, D)) * 0.01).astype(dt),
        "u_bonus": (jax.random.normal(ks[6], (D,)) * 0.1).astype(jnp.float32),
        "wo": (jax.random.normal(ks[7], (D, D)) * s).astype(dt),
        # channel mix
        "ck": (jax.random.normal(ks[0], (D, cfg.d_ff)) * s).astype(dt),
        "cv": (jax.random.normal(ks[1], (cfg.d_ff, D)) * cfg.d_ff ** -0.5).astype(dt),
        "cmix": (jax.random.normal(ks[2], (D,)) * 0.01).astype(dt),
    }


def _token_shift(x, last):
    """shift right by one along S; ``last`` is (B,1,D) carry."""
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def _wkv_chunk(r, k, v, w, u, s0):
    """Chunked WKV6 recurrence (per head).  r,k,v: (B,H,C,hd); w: decay in
    (0,1) (B,H,C,hd); u: (H,hd) bonus; s0: (B,H,hd,hd) carried state.
    Returns (out (B,H,C,hd), s1).  fp32 math."""
    B, H, C, hd = r.shape
    logw = jnp.log(w)
    cw = jnp.cumsum(logw, axis=2)                        # (B,H,C,hd)
    # decay from token j (exclusive) to token t: exp(cw[t] - cw[j])
    # intra-chunk: out[t] += sum_{j<t} r[t]·(exp(cw[t-1]-cw[j]) k[j]) v[j]
    cw_prev = cw - logw                                   # cw[t-1]
    rd = r * jnp.exp(cw_prev)                             # (B,H,C,hd)
    kd = k * jnp.exp(-cw)
    att = jnp.einsum("bhtd,bhjd->bhtj", rd, kd)
    mask = jnp.tril(jnp.ones((C, C)), -1)
    att = att * mask
    out = jnp.einsum("bhtj,bhje->bhte", att, v)
    # bonus (current token)
    out = out + jnp.einsum("bhtd,bhtd,bhte->bhte", r, k * u[None, :, None, :], v)
    # carried state
    out = out + jnp.einsum("bhtd,bhde->bhte", rd, s0)
    # state update: s1 = diag(exp(cw[-1])) s0 + sum_j exp(cw[-1]-cw[j]) k_j v_j^T
    wtot = jnp.exp(cw[:, :, -1])                          # (B,H,hd)
    s1 = s0 * wtot[..., None] + jnp.einsum(
        "bhjd,bhje->bhde", k * jnp.exp(cw[:, :, -1:] - cw), v)
    return out, s1


def rwkv_time_mix(cfg: ArchConfig, p, x, shift_last, s0, chunk=128):
    B, S, D = x.shape
    H = D // cfg.rwkv_head_dim
    hd = cfg.rwkv_head_dim
    h = rms_norm(x, p["norm_a"], cfg.norm_eps)
    prev = _token_shift(h, shift_last)
    mix = jax.nn.sigmoid(p["mix"])                        # (5, D)
    feats = [h + (prev - h) * mix[i] for i in range(5)]
    r = feats[0] @ p["wr"]
    k = feats[1] @ p["wk"]
    v = feats[2] @ p["wv"]
    g = jax.nn.silu(feats[3] @ p["wg"])
    w = jnp.exp(-jnp.exp((feats[4] @ p["wdecay"]).astype(jnp.float32) - 4.0))

    def heads(t):
        return t.reshape(B, S, H, hd).swapaxes(1, 2)      # (B,H,S,hd)

    rh, kh, vh, wh = map(lambda t: heads(t).astype(jnp.float32),
                         (r, k, v, w))
    u = p["u_bonus"].reshape(H, hd)
    chunk = min(chunk, S)
    assert S % chunk == 0
    nch = S // chunk

    def step(s, args):
        rc, kc, vc, wc = args
        out, s1 = _wkv_chunk(rc, kc, vc, wc, u, s)
        return s1, out

    split = lambda t: t.reshape(B, H, nch, chunk, hd).swapaxes(0, 2).swapaxes(1, 2)
    s_fin, outs = jax.lax.scan(step, s0, tuple(map(split, (rh, kh, vh, wh))))
    out = outs.swapaxes(1, 2).swapaxes(0, 2).reshape(B, H, S, hd)
    out = out.swapaxes(1, 2).reshape(B, S, D).astype(x.dtype) * g
    y = x + (out @ p["wo"])
    return y, h[:, -1:], s_fin


def rwkv_channel_mix(cfg: ArchConfig, p, x, shift_last):
    h = rms_norm(x, p["norm_f"], cfg.norm_eps)
    prev = _token_shift(h, shift_last)
    mixed = h + (prev - h) * jax.nn.sigmoid(p["cmix"])
    v = jnp.square(jax.nn.relu(mixed @ p["ck"])) @ p["cv"]
    return x + v, h[:, -1:]


def rwkv_forward(cfg: ArchConfig, p, x):
    B, S, D = x.shape
    H = D // cfg.rwkv_head_dim
    s0 = jnp.zeros((B, H, cfg.rwkv_head_dim, cfg.rwkv_head_dim), jnp.float32)
    zero = jnp.zeros((B, 1, D), x.dtype)
    y, _, _ = rwkv_time_mix(cfg, p, x, zero, s0)
    y, _ = rwkv_channel_mix(cfg, p, y, zero)
    return y


def rwkv_decode(cfg: ArchConfig, p, x, cache):
    y, sa, s1 = rwkv_time_mix(cfg, p, x, cache["shift_a"], cache["s"], chunk=1)
    y, sf = rwkv_channel_mix(cfg, p, y, cache["shift_f"])
    return y, {"shift_a": sa, "shift_f": sf, "s": s1}


def init_rwkv_cache(cfg: ArchConfig, B, dt):
    D = cfg.d_model
    H = D // cfg.rwkv_head_dim
    return {"shift_a": jnp.zeros((B, 1, D), dt),
            "shift_f": jnp.zeros((B, 1, D), dt),
            "s": jnp.zeros((B, H, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                           jnp.float32)}


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)
# ---------------------------------------------------------------------------


def init_cross_attn(cfg: ArchConfig, key):
    return init_attn(cfg, key)


def cross_attn_forward(cfg: ArchConfig, p, x, enc_out):
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    T = enc_out.shape[1]
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    o = _sdpa(cfg, q, _repeat_kv(k, H // K), _repeat_kv(v, H // K),
              jnp.ones((B, 1, S, T), bool), x.dtype)
    return x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
