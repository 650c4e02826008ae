"""Step kind ``forward_inverse``: a closed loop with one caller.  Each step
runs ``forward``, then ``inverse`` on its spectrum, and ends in
``block_until_ready``; the next forward takes the inverse's output, as a
solver's time step takes the last one's field.

Checked once the window has closed:

- ``forward_err``: the last step's spectrum against the reference's DFT
  of that step's own input, the widest gap over the widest reference
  value.  It covers every transpose's placement of the data.
- ``round_trip_err``: the last step's output against that step's input,
  which the exact inverse of the exact forward gives back.
- ``chain_err_per_step``: the last step's output against the first
  input, made again from the seed, over the number of steps: every round
  trip of the window, accumulated, per round trip.  A fault that maps
  its own output to itself (a projection) passes the last step's checks
  and fails this one.  Rounding drifts by at most about one round trip's
  error per step, so a program that takes more steps in the window reads
  no higher.
- ``layout_mismatches``: the spectrum and the output against the layouts
  the configuration states.
"""

from __future__ import annotations

import time

import jax
from jax.profiler import TraceAnnotation

from bench import compare, reference, traffic

NUMBERS = ("forward_err", "round_trip_err", "chain_err_per_step",
           "layout_mismatches")


class Step:
    def __init__(self, system, cfg: dict, traffic_cfg: dict, seed: int,
                 cfg_module=None):
        if cfg["problem"] != "c2c":
            raise ValueError("forward_inverse drives a c2c plan")
        self.sys = system
        self.cfg = cfg
        self.traffic = traffic_cfg
        self.seed = seed
        self.shape = tuple(cfg["shape"])
        layout = cfg.get("layout") or {}
        self.in_spec = tuple(layout["input"]) if layout.get("input") else None
        self.out_spec = (tuple(layout["output"]) if layout.get("output")
                         else None)
        self.last = None

    def _input(self):
        return traffic.fields(self.seed, 1, self.shape, self.cfg["dtype"],
                              self.sys.input_sharding,
                              self.traffic.get("input"))[0]

    def setup(self) -> None:
        self.x = self._input()
        for _ in range(int(self.traffic.get("warmup", 1))):
            jax.block_until_ready(self.sys.inverse(self.sys.forward(self.x)))

    def window(self, seconds: float) -> tuple[int, float]:
        """Steps until ``seconds`` have passed; (steps, elapsed seconds)."""
        fwd, inv = self.sys.forward, self.sys.inverse
        x, self.x = self.x, None
        steps = 0
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            while True:
                with TraceAnnotation("bench.step"):
                    with TraceAnnotation("bench.call.forward"):
                        y = fwd(x)
                    with TraceAnnotation("bench.call.inverse"):
                        xn = inv(y)
                    with TraceAnnotation("bench.wait"):
                        xn.block_until_ready()
                steps += 1
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
                x = xn
        self.last = (x, y, xn, steps)
        return steps, elapsed

    def release(self) -> None:
        self.sys.release()

    def check(self) -> dict:
        x_prev, y, x_n, steps = self.last
        self.last = None
        mesh = self.sys.mesh
        layout = (compare.layout_mismatches(y, mesh, self.out_spec)
                  + compare.layout_mismatches(x_n, mesh, self.in_spec))
        x0 = self._input()
        chain = compare.rel_gap(x_n, x0) / steps
        del x0
        round_trip = compare.rel_gap(x_n, x_prev)
        del x_n
        want = reference.jitted("fft3", "highest", mesh,
                                self.in_spec)(x_prev)
        del x_prev
        fwd = compare.rel_gap(y, want)
        return {"forward_err": fwd, "round_trip_err": round_trip,
                "chain_err_per_step": chain, "layout_mismatches": layout}
