"""croft-1024: the CROFT paper's largest grid (arXiv:2002.04896), 1024^3
complex, pencil decomposition over a 2x2 mesh of chips."""

from __future__ import annotations

import math

import numpy as np


def least_hbm_bytes(cfg: dict, traffic: dict) -> int:
    """Least HBM bytes of one step on each chip: the forward and the
    inverse each read their input once and write their output once, and
    each chip holds 1/chips of every array."""
    if traffic["step"] != "forward_inverse":
        raise ValueError(f"no byte count for step kind {traffic['step']!r}")
    chips = math.prod(cfg["mesh"]["shape"])
    field = math.prod(cfg["shape"]) * np.dtype(cfg["dtype"]).itemsize
    return 4 * field // chips
