"""Where the Pallas kernels run, and how their blocks are sized.

One resolver for every kernel in this package: a kernel compiles to
Mosaic on a TPU backend and runs in the Pallas interpreter elsewhere,
unless the caller forces ``interpret``.  No kernel defaults to the
interpreter on its own, so nothing runs interpreted on a TPU.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: f32 bytes a kernel's live blocks may hold per grid step (input and
#: output windows plus intermediates).  Mosaic double-buffers the
#: windows, so this stays well under v5e's 16 MiB scoped VMEM.
BLOCK_BUDGET_BYTES = 4 * 1024 * 1024

#: Mosaic tiles the last two block dims in (8, 128) f32 units: a block
#: dim must be a multiple of these or span the whole array dim.
SUBLANES = 8
LANES = 128


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Compiled on TPU, interpreter elsewhere, unless the caller forces it."""
    if interpret is not None:
        return interpret
    return not on_tpu()


def pick_block_rows(rows: int, row_elems: int, n_planes: int,
                    budget: int = BLOCK_BUDGET_BYTES) -> int:
    """Rows per block: the largest divisor of ``rows`` that is a multiple
    of 8 and keeps ``n_planes`` f32 planes of ``row_elems`` under
    ``budget`` bytes.  ``rows`` itself when it fits, or when no such
    divisor exists (a block spanning the whole dim is always legal).
    VMEM pads a row to whole 128-lane vregs, so that is what is counted."""
    padded = -(-row_elems // LANES) * LANES
    cap = max(SUBLANES, budget // (n_planes * padded * 4))
    if rows <= cap:
        return rows
    for d in range(cap - cap % SUBLANES, 0, -SUBLANES):
        if rows % d == 0:
            return d
    return rows


def pick_block_cols(cols: int, rows: int, n_planes: int,
                    budget: int = BLOCK_BUDGET_BYTES) -> int:
    """Columns per block for a ``rows``-row window: the largest divisor of
    ``cols`` that is a multiple of 128 and fits ``budget``; ``cols``
    itself when it fits or is not a multiple of 128."""
    cap = max(LANES, budget // (n_planes * rows * 4))
    if cols <= cap or cols % LANES:
        return cols
    for d in range(cap - cap % LANES, LANES, -LANES):
        if cols % d == 0:
            return d
    return LANES


def f32_outputs(shape, count: int, *operands) -> list:
    """``count`` f32 ``out_shape`` entries of ``shape`` that vary over the
    mesh axes the operands vary over.  Inside ``shard_map`` a kernel's
    outputs must declare them; outside it the set is empty."""
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    return [jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)] * count
