"""repro.resil — seeded fault injection and degradation ladders.

The robustness layer (ISSUE 10): production serving must survive failed
plan builds, corrupt wisdom files, transient dispatch errors and
poisoned payloads without hanging a single future — and CI must be able
to *prove* it, deterministically.  Three pieces:

  * :mod:`repro.resil.inject` — a scripted, seeded fault-injection
    plane with named sites threaded through plan build, batch dispatch,
    wisdom IO, measure mode and executor outputs.  Zero-cost no-op when
    disarmed (the ``repro.obs`` tracer contract: enabling cannot change
    compiled HLO, pinned in tests).
  * :mod:`repro.resil.degrade` — the plan degradation ladder (searched
    schedule -> fixed tuned -> default/alltoall/K1; packed r2c ->
    embed).  ``PlanCache`` walks it when a plan's build fails or its
    dispatches keep failing (quarantine), and every rung stays bitwise
    equal on finite inputs.
  * :mod:`repro.resil.fault` — the SIGTERM ``PreemptionHandler`` the
    service drains on, and a step-time ``StragglerMonitor``.

``tests/test_resil.py::test_chaos_script_*`` drives a seeded fault
script through the transform service on an 8-device mesh and holds it
to exact counter equality: every injected fault maps to exactly one
observed quarantine / retry / shed / degradation event.
"""

from repro.resil import degrade, fault, inject  # noqa: F401
from repro.resil.inject import (CrashMidWrite, FaultPlan,  # noqa: F401
                                FaultSpec, InjectedFault, TransientFault,
                                injection, seeded_times)

__all__ = [
    "CrashMidWrite", "FaultPlan", "FaultSpec", "InjectedFault",
    "TransientFault", "degrade", "fault", "inject", "injection",
    "seeded_times",
]
