"""conv_pool_hd: a 3 x 3 blur, then a 2 x 2 stride-2 max-pool, on a 1080p
frame.

The program is the benchmark's own rectangular copy of
``programs.conv_pool``, built with the program's ``ProgramBuilder``, so
what the compiler is given is what a user writes; an edit to
``programs.py`` does not move it.  The pool reads the blur at twice the
rate the blur writes it, the producer-consumer rate mismatch that the
streamed lowering decimates with strided takes.

    conv[i, j] = sum_{u,v < 3} w[u][v] img[i+u, j+v]           i < rows,   j < cols
    pool[i, j] = max_{u,v < 2} conv[2i+u, 2j+v]                i < rows/2, j < cols/2
"""
from __future__ import annotations

import numpy as np

from repro.core.ir import Program, ProgramBuilder

# the "bram" storage preset of programs.py: row-partitioned, one write and
# three read ports
_STORAGE = {"bram": dict(partition=(0,), ports=("w", "r", "r", "r"))}


def program(cfg: dict, consts: dict, dse: bool = False) -> Program:
    """The blur and pool at the deployment size, or at the DSE size."""
    rows, cols = ((cfg["dse"]["rows"], cfg["dse"]["cols"]) if dse
                  else (cfg["rows"], cfg["cols"]))
    if rows % 2 or cols % 2:
        raise ValueError(f"the 2 x 2 pool needs even sizes, got "
                         f"{rows} x {cols}")
    w = consts["weights"]
    st = _STORAGE[cfg["storage"]]
    b = ProgramBuilder("conv_pool")
    b.array("img", (rows + 2, cols + 2), is_arg=True, **st)
    b.array("conv", (rows, cols), **st)
    b.array("pool", (rows // 2, cols // 2), is_arg=True, **st)
    with b.loop("cvi", 0, rows) as i:
        with b.loop("cvj", 0, cols) as j:
            b.store("conv", b.sum_tree([b.mul(b.load("img", i + u, j + v),
                                              b.const(w[u][v]))
                                        for u in range(3) for v in range(3)]),
                    i, j)
    with b.loop("pli", 0, rows // 2) as i:
        with b.loop("plj", 0, cols // 2) as j:
            m = None
            for u in range(2):
                for v in range(2):
                    x = b.load("conv", i * 2 + u, j * 2 + v)
                    m = x if m is None else b.arith("max", m, x)
            b.store("pool", m, i, j)
    return b.build()


def consts(cfg: dict, rng: np.random.Generator | None = None) -> dict:
    """The configuration's weights, or, given ``rng``, all nine drawn as a
    developer's edit would change them (``draw_weights``)."""
    if rng is None:
        return {"weights": [[float(x) for x in row] for row in cfg["weights"]]}
    lo, hi = cfg["draw_weights"]["uniform"]
    return {"weights": rng.uniform(lo, hi, (3, 3)).tolist()}


def reference(arrays: dict, consts: dict, xp=np, dtype=np.float64) -> dict:
    """The blur and the pool in plain array code.  With numpy and float64
    it is the reference; with ``jax.numpy`` and bfloat16 it is the
    lower-precision control."""
    img = xp.asarray(arrays["img"], dtype)
    rows, cols = img.shape[0] - 2, img.shape[1] - 2
    conv = None
    for u in range(3):
        for v in range(3):
            term = xp.asarray(consts["weights"][u][v], dtype) \
                * img[u:u + rows, v:v + cols]
            conv = term if conv is None else conv + term
    blocks = conv.reshape(rows // 2, 2, cols // 2, 2)
    return {"pool": blocks.max(axis=(1, 3))}


def counts(cfg: dict) -> tuple[int, int]:
    """(operations, HBM bytes) the algorithm needs for one frame: nine
    multiplies and eight adds at every blur point, three maxes at every
    pool point; the input read once and the pool written once (the blur
    never leaves the chip)."""
    rows, cols = cfg["rows"], cfg["cols"]
    ops = 17 * rows * cols + 3 * (rows // 2) * (cols // 2)
    item = np.dtype(cfg["dtype"]).itemsize
    nbytes = item * ((rows + 2) * (cols + 2) + (rows // 2) * (cols // 2))
    return ops, nbytes
