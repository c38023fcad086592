"""two_mm_medium: PolyBench's 2mm at its MEDIUM sizes.

The program is the benchmark's own copy of ``programs.two_mm``, taken to
the rectangular PolyBench shapes (NI, NJ, NK, NL):

    tmp[i, j] += A[i, k] B[k, j]    i < NI, j < NJ, k < NK
    D[i, l]   += tmp[i, j] C[j, l]  i < NI, l < NL, j < NJ

Both accumulators are function arguments that start at zero, so this is
PolyBench's ``tmp = alpha A B; D = beta D + tmp C`` with alpha = 1 and D
zero at the start.
"""
from __future__ import annotations

import numpy as np

from repro.core.ir import Program, ProgramBuilder


def program(cfg: dict, consts: dict, dse: bool = False) -> Program:
    """2mm at the deployment size, or at the DSE size."""
    src = cfg["dse"] if dse else cfg
    ni, nj, nk, nl = src["NI"], src["NJ"], src["NK"], src["NL"]
    b = ProgramBuilder("two_mm")
    b.array("A", (ni, nk), is_arg=True, ports=("r", "r"))
    b.array("B", (nk, nj), is_arg=True, ports=("r", "r"))
    b.array("C", (nj, nl), is_arg=True, ports=("r", "r"))
    b.array("tmp", (ni, nj), is_arg=True, ports=("w", "r"))
    b.array("D", (ni, nl), is_arg=True, ports=("w", "r"))
    for tag, (x, w, dst, n1, n2, nr) in (("p", ("A", "B", "tmp", ni, nj, nk)),
                                         ("c", ("tmp", "C", "D", ni, nl, nj))):
        with b.loop(f"{tag}i", 0, n1) as i:
            with b.loop(f"{tag}j", 0, n2) as j:
                with b.loop(f"{tag}k", 0, nr) as k:
                    acc = b.load(dst, i, j)
                    prod = b.mul(b.load(x, i, k), b.load(w, k, j))
                    b.store(dst, b.add(acc, prod), i, j)
    return b.build()


def consts(cfg: dict, rng: np.random.Generator | None = None) -> dict:
    """2mm has no constants to edit."""
    if rng is not None:
        raise ValueError("two_mm_medium has no constants to draw")
    return {}


def reference(arrays: dict, consts: dict, xp=np, dtype=np.float64) -> dict:
    """2mm in plain array code.  With numpy and float64 it is the
    reference; with ``jax.numpy`` and bfloat16 it is the lower-precision
    control."""
    a, b, c = (xp.asarray(arrays[k], dtype) for k in ("A", "B", "C"))
    tmp = xp.asarray(arrays["tmp"], dtype) + a @ b
    d = xp.asarray(arrays["D"], dtype) + tmp @ c
    return {"tmp": tmp, "D": d}


def counts(cfg: dict) -> tuple[int, int]:
    """(operations, HBM bytes) the algorithm needs for one problem: a
    multiply and an add per (i, j, k) and per (i, l, j); A, B and C read,
    tmp and D read as zeros and written."""
    ni, nj, nk, nl = cfg["NI"], cfg["NJ"], cfg["NK"], cfg["NL"]
    ops = 2 * (ni * nj * nk + ni * nl * nj)
    item = np.dtype(cfg["dtype"]).itemsize
    nbytes = item * (ni * nk + nk * nj + nj * nl + 2 * ni * nj + 2 * ni * nl)
    return ops, nbytes
