"""The comparison that decides ``correct``.

One number per run, ``rel_err``: over every sampled answer and every
output array the configuration names, the largest elementwise gap to the
float64 reference, as a share of the largest magnitude of that reference
output.  A missing output, a wrong shape or a value that is not finite
reads ``inf``.  The limit is the configuration's ``limits.rel_err``.
"""
from __future__ import annotations

import math

import numpy as np


def rel_err(got: dict, ref: dict, outputs) -> float:
    worst = 0.0
    for name in outputs:
        if name not in got:
            return math.inf
        g = np.asarray(got[name], np.float64)
        r = np.asarray(ref[name], np.float64)
        if g.shape != r.shape or not np.isfinite(g).all():
            return math.inf
        scale = float(np.abs(r).max()) or 1.0
        worst = max(worst, float(np.abs(g - r).max()) / scale)
    return worst


def check_lines(checks: dict) -> list[str]:
    """One plain line per compared number: its name, value and limit."""
    return [f"check {name}: {c['value']!r} limit {c['limit']!r}"
            for name, c in checks.items()]
