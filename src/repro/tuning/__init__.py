"""repro.tuning — FFTW-style autotuning planner for the distributed 3-D FFT.

CROFT's option study (§5.1) and its FFTW3 comparison are ultimately about
*plan selection*: the same transform can be run with different
decompositions (slab/pencil/cell), overlap depths (K), local 1-D kernels,
output layouts, and transpose implementations, and the right combination
depends on shape, mesh, dtype, and hardware.  This package chooses it,
mapping directly onto FFTW's planner design:

  FFTW concept          here
  --------------------  ---------------------------------------------------
  planner search space  ``candidates.enumerate_candidates`` — every valid
                        (Decomposition, FFTOptions) pair for (shape, mesh),
                        filtered by divisibility/overlap constraints
  FFTW_ESTIMATE         ``mode="model"`` — ``cost_model.analytic_cost``
                        builds the candidate's actual stage schedule
                        (``repro.core.schedule``, the same object the
                        executor runs) and walks it: per-stage FFT sizes
                        and transpose bytes, effective overlap-K,
                        collective launch counts — with zero execution;
                        optional HLO-derived collective counts via
                        ``cost_model.hlo_collectives``
  FFTW_PATIENT          ``mode="measure"`` — ``measure.measure_candidate``
                        compiles and wall-clocks the model-ranked top-k
                        (plus the untuned default) on the live mesh
  wisdom import/export  ``wisdom.Wisdom`` — JSON store keyed by
                        shape|mesh|dtype|backend[|problem]; ``mode="wisdom"``
                        reuses a stored plan without re-searching, and stores
                        can be merged across processes/hosts
                        (``python -m repro.tuning.wisdom merge``, with a
                        shipped seed file via ``--seed``)

Problem classes: ``problem="c2c"`` (default) and ``problem="r2c"`` — the
real transform is a first-class citizen: its candidates carry a
packed/embed strategy axis (the two-for-one pipelines of ``repro.real``,
pencil and slab alike, vs the embedding fallback), the schedule-derived
cost model charges the packed stages at their true half-volume sizes,
measurement runs real-input plans, and wisdom keys gain a problem
dimension.  The ``_grad`` variants (``"c2c_grad"``/``"r2c_grad"``) plan a
*training step*: same physical search space, but the cost model prices
the forward schedule **plus** its adjoint (``repro.grad``), measurement
races ``jax.value_and_grad`` through the plan, and the wisdom key gains a
trailing ``|grad`` dimension.  ``heterogeneous_impls=True`` additionally
searches per-stage ``local_impl`` 3-tuples, and ``batch=B`` plans for
vmapped transforms (volume terms scale by B, collective launch counts do
not; the wisdom key gains ``|b{B}``).

The collective cost constants (alpha latency, beta inverse bandwidth)
are the priors ``cost_model.COLLECTIVE_LATENCY_S`` and
``1 / cost_model.PRIOR_LINK_BW``; fitting them to chip traces is ROADMAP
queue 1, item 8.

Entry points: :func:`tune` below, and ``Croft3D.tuned(...)`` /
``Croft3D(..., tune="model")`` in ``repro.core.api``.
"""

from repro.tuning.candidates import (PROBLEMS, Candidate, default_candidate,
                                     decompositions_for, enumerate_candidates,
                                     split_grad)
from repro.tuning.cost_model import (CostBreakdown, analytic_cost,
                                     hlo_collectives, per_stage_costs,
                                     rank_candidates)
from repro.tuning.measure import (measure_candidate, time_forward,
                                  time_train_step)
from repro.tuning.planner import MODES, TuneResult, tune, upgrade_wisdom
from repro.tuning.wisdom import (Wisdom, WisdomEntry, load_seed,
                                 merge_entries, wisdom_key)

__all__ = [
    "Candidate", "CostBreakdown", "MODES", "PROBLEMS", "TuneResult",
    "Wisdom", "WisdomEntry", "analytic_cost", "decompositions_for",
    "default_candidate", "enumerate_candidates",
    "hlo_collectives", "load_seed", "measure_candidate", "merge_entries",
    "per_stage_costs", "rank_candidates", "split_grad", "time_forward",
    "time_train_step", "tune", "upgrade_wisdom", "wisdom_key",
]
