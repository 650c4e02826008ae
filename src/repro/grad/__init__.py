"""repro.grad — adjoint schedules: the differentiable distributed FFT.

Two layers:

``adjoint``   a pure ``Schedule -> Schedule`` transform (reverse the
              stage order, swap each transpose's split/concat axes, map
              each local FFT and packed stage op to its transpose),
              validated by the same symbolic layout propagation that
              checks forward schedules.
``vjp``       ``jax.custom_vjp`` wiring that runs the adjoint schedule
              as the backward pass of every entry point — plan-reusing,
              residual-free for the linear transforms, and the only way
              to differentiate the pairwise transpose at all (XLA has no
              rule for ``optimization_barrier``).
``filter``    the learned spectral filter trained through a plan: the
              workload ``Croft3D.tuned(..., grad=True)`` plans for.

``fft3d``/``ifft3d``/``rfft3d``/``irfft3d`` and the ``Croft3D`` methods
pick this up automatically; nothing here needs to be called directly
unless you are composing adjoints yourself.
"""

from repro.grad.adjoint import (PackTwoT, RepackHalvesT, SplitPairsT,
                                UnpackTwoT, adjoint_ops, adjoint_schedule,
                                fold_dc_plane_t, unfold_dc_plane_t)
from repro.grad import vjp

__all__ = [
    "PackTwoT", "RepackHalvesT", "SplitPairsT", "UnpackTwoT",
    "adjoint_ops", "adjoint_schedule", "fold_dc_plane_t",
    "unfold_dc_plane_t", "vjp",
]
