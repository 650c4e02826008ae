"""Process-level faults: preemption handling and straggler detection.

  * planned preemption  -> a SIGTERM handler flips a flag;
    ``TransformService(preemption=PreemptionHandler())`` serves what is
    pending, stops cleanly and refuses new work;
  * stragglers          -> per-step wall times feed an EMA z-score
    monitor; flagged steps are logged and passed to a policy hook.
"""

from __future__ import annotations

import dataclasses
import math
import signal
import time
from typing import Callable, Optional


class PreemptionHandler:
    """SIGTERM/SIGINT -> graceful drain-and-exit flag."""

    def __init__(self, signals=(signal.SIGTERM,)):
        self._requested = False
        self._installed = False
        self._signals = signals

    def install(self):
        if self._installed:
            return
        for sig in self._signals:
            try:
                signal.signal(sig, self._handle)
            except ValueError:
                pass  # non-main thread (tests)
        self._installed = True

    def _handle(self, signum, frame):
        self._requested = True

    @property
    def preemption_requested(self) -> bool:
        return self._requested


@dataclasses.dataclass
class StepStats:
    step: int
    seconds: float
    z_score: float
    is_straggler: bool


class StragglerMonitor:
    """EMA mean/variance of step wall time; flags outliers.

    On a multi-host deployment every host reports its step time into a
    cross-host allgather (cheap: one float); here the single-process variant
    monitors the global step and exposes the same policy hook.
    """

    def __init__(self, z_threshold: float = 4.0, ema: float = 0.95,
                 warmup_steps: int = 5,
                 on_straggler: Optional[Callable[[StepStats], None]] = None):
        self.z = z_threshold
        self.ema = ema
        self.warmup = warmup_steps
        self.mean = 0.0
        self.var = 0.0
        self.n = 0
        self.flagged: list[StepStats] = []
        self.on_straggler = on_straggler
        self._t0: Optional[float] = None

    def start_step(self):
        self._t0 = time.monotonic()

    def end_step(self, step: int) -> StepStats:
        dt = time.monotonic() - (self._t0 or time.monotonic())
        self.n += 1
        if self.n <= self.warmup:
            self.mean = dt if self.n == 1 else \
                (self.mean * (self.n - 1) + dt) / self.n
            self.var = max(self.var, (dt - self.mean) ** 2)
            return StepStats(step, dt, 0.0, False)
        sd = math.sqrt(self.var) if self.var > 0 else max(self.mean * 0.05, 1e-9)
        z = (dt - self.mean) / sd
        is_straggler = z > self.z
        self.mean = self.ema * self.mean + (1 - self.ema) * dt
        self.var = self.ema * self.var + (1 - self.ema) * (dt - self.mean) ** 2
        stats = StepStats(step, dt, z, is_straggler)
        if is_straggler:
            self.flagged.append(stats)
            if self.on_straggler:
                self.on_straggler(stats)
        return stats
