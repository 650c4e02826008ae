"""The names CROFT puts on the program that runs on the chip.

Every device op of a transform sits under exactly one *role* scope, a
``croft.<role>`` component of its ``op_name`` metadata:

  ``croft.dft``        DFT contractions: the einsums and twiddle multiplies
                       of the local 1-D FFTs, whichever implementation
  ``croft.relayout``   data movement on the chip: the axis moves around
                       each 1-D FFT, the four-step's per-level swaps, chunk
                       split/concatenate, the transpose's pack/unpack, the
                       real transform's pack/unpack/fold/unfold
  ``croft.transpose``  the collective leg: all-to-alls, ppermute rounds and
                       the reshards of the packed real pipeline
  ``croft.scale``      normalisation scales and k-space multiplies

Roles never nest.  Around them, ``stage`` adds the schedule stage and,
for a K-chunked stage, the chunk: ``croft.stage.<name>/k<i>``.  Each
jitted entry of ``Croft3D`` is a named function (``croft_forward``, ...),
so the XLA module names tell the programs apart.

All of this is ``jax.named_scope``: it changes ``op_name`` metadata and
nothing that is compiled.
"""

from __future__ import annotations

import contextlib
import functools

import jax

DFT = "croft.dft"
RELAYOUT = "croft.relayout"
TRANSPOSE = "croft.transpose"
SCALE = "croft.scale"
STAGE_PREFIX = "croft.stage."


def role(name: str):
    """Decorator: the whole function runs under role scope ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


@contextlib.contextmanager
def stage(name: str, chunk: int | None = None):
    """Scope of one schedule stage (``croft.stage.<name>``), and of chunk
    ``chunk`` of it (``k<chunk>``) when the stage is K-chunked."""
    with jax.named_scope(STAGE_PREFIX + name):
        if chunk is None:
            yield
        else:
            with jax.named_scope(f"k{chunk}"):
                yield
