"""blur_hd: the paper's blur-x -> blur-y chain on a 1080p frame.

The program is the benchmark's own copy of ``programs.blur_chain``, taken
to any (rows, cols): blur-x reads 3 columns of the input and covers every
input row, blur-y reads 3 rows of blur-x.  It is built with the program's
``ProgramBuilder``, so what the compiler is given is what a user writes;
an edit to ``programs.py`` does not move it.

    bx[i, j] = w0 img[i, j] + w1 img[i, j+1] + w2 img[i, j+2]   i < rows+2, j < cols
    by[i, j] = w0 bx[i, j]  + w1 bx[i+1, j]  + w2 bx[i+2, j]    i < rows,   j < cols
"""
from __future__ import annotations

import numpy as np

from repro.core.ir import Program, ProgramBuilder

# the "bram" storage preset of programs.py: row-partitioned, one write and
# three read ports
_STORAGE = {"bram": dict(partition=(0,), ports=("w", "r", "r", "r"))}


def program(cfg: dict, consts: dict, dse: bool = False) -> Program:
    """The chain at the deployment size, or at the DSE size."""
    rows, cols = ((cfg["dse"]["rows"], cfg["dse"]["cols"]) if dse
                  else (cfg["rows"], cfg["cols"]))
    w = consts["weights"]
    taps = len(w)
    st = _STORAGE[cfg["storage"]]
    b = ProgramBuilder("blur_chain")
    b.array("img", (rows + taps - 1, cols + taps - 1), is_arg=True, **st)
    b.array("bx", (rows + taps - 1, cols), **st)
    b.array("by", (rows, cols), is_arg=True, **st)
    with b.loop("bxi", 0, rows + taps - 1) as i:
        with b.loop("bxj", 0, cols) as j:
            b.store("bx", b.sum_tree([b.mul(b.load("img", i, j + v),
                                            b.const(w[v]))
                                      for v in range(taps)]), i, j)
    with b.loop("byi", 0, rows) as i:
        with b.loop("byj", 0, cols) as j:
            b.store("by", b.sum_tree([b.mul(b.load("bx", i + u, j),
                                            b.const(w[u]))
                                      for u in range(taps)]), i, j)
    return b.build()


def consts(cfg: dict, rng: np.random.Generator | None = None) -> dict:
    """The configuration's weights, or, given ``rng``, weights drawn as a
    developer's edit would change them (``draw_weights``)."""
    if rng is None:
        return {"weights": [float(x) for x in cfg["weights"]]}
    lo, hi = cfg["draw_weights"]["uniform"]
    return {"weights": [float(x) for x in rng.uniform(lo, hi, cfg["taps"])]}


def reference(arrays: dict, consts: dict, xp=np, dtype=np.float64) -> dict:
    """The chain in plain array code.  With numpy and float64 it is the
    reference; with ``jax.numpy`` and bfloat16 it is the lower-precision
    control."""
    img = xp.asarray(arrays["img"], dtype)
    w = [xp.asarray(x, dtype) for x in consts["weights"]]
    taps = len(w)
    rows, cols = img.shape[0] - taps + 1, img.shape[1] - taps + 1
    bx = w[0] * img[:, 0:cols]
    for v in range(1, taps):
        bx = bx + w[v] * img[:, v:v + cols]
    by = w[0] * bx[0:rows]
    for u in range(1, taps):
        by = by + w[u] * bx[u:u + rows]
    return {"by": by}


def counts(cfg: dict) -> tuple[int, int]:
    """(operations, HBM bytes) the algorithm needs for one frame: a multiply
    and an add per tap but the first's add (5 for 3 taps) at every blur-x
    and blur-y point; the input read once and the output written once."""
    rows, cols, taps = cfg["rows"], cfg["cols"], cfg["taps"]
    per_point = 2 * taps - 1
    ops = per_point * ((rows + taps - 1) * cols + rows * cols)
    item = np.dtype(cfg["dtype"]).itemsize
    nbytes = item * ((rows + taps - 1) * (cols + taps - 1) + rows * cols)
    return ops, nbytes
