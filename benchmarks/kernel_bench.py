"""Kernel microbenchmarks: Pallas (interpret) vs jnp reference on CPU.

Wall-clock on this container measures the *reference* path meaningfully and
the kernels only structurally (interpret mode is a Python interpreter); the
derived column therefore reports correctness deltas + modeled VMEM working
sets, not CPU time ratios."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _time(fn, *args, n=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / n * 1e6


def run(emit):
    print("# === Pallas kernels (interpret-mode correctness + ref timing) ===")
    rows = []
    # flash attention
    B, H, S, hd = 1, 2, 256, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(ks[i], (B, H, S, hd)) for i in range(3))
    us = _time(lambda a, b, c: ref.flash_attention_ref(a, b, c), q, k, v)
    got = ops.flash_attention(q, k, v, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref.flash_attention_ref(q, k, v))))
    rows.append(("kernel.flash_attention.ref_us", us, f"maxerr={err:.1e}"))
    vmem = (128 * hd + 2 * 128 * hd + 128 * 128) * 4
    rows.append(("kernel.flash_attention.vmem_bytes_per_block", 0.0, vmem))
    # stencil pipeline
    img = jax.random.normal(jax.random.key(1), (66, 130))
    wx = jnp.asarray([0.25, 0.5, 0.25])
    us = _time(lambda a: ref.stencil_pipeline_ref(a, wx, wx), img)
    got = ops.stencil_pipeline(img, wx, wx, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref.stencil_pipeline_ref(img, wx, wx))))
    rows.append(("kernel.stencil_pipeline.ref_us", us, f"maxerr={err:.1e}"))
    from repro.kernels.stencil_pipeline import _stencil_codegen_config
    br, halo = _stencil_codegen_config()
    rows.append(("kernel.stencil_pipeline.dse_config", 0.0,
                 f"block_rows={br};halo={halo}"))
    # wkv6
    B, H, S, hd = 1, 2, 128, 64
    ks = jax.random.split(jax.random.key(2), 4)
    r, k2, v2 = (jax.random.normal(ks[i], (B, H, S, hd)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, S, hd))) * 0.5 + 0.45
    u = jnp.zeros((H, hd))
    us = _time(lambda *a: ref.wkv6_ref(*a)[0], r, k2, v2, w, u)
    got = ops.wkv6(r, k2, v2, w, u, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref.wkv6_ref(r, k2, v2, w, u)[0])))
    rows.append(("kernel.wkv6.ref_us", us, f"maxerr={err:.1e}"))
    emit(rows)
