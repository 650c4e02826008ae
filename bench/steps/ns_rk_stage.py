"""Step kind ``ns_rk_stage``: a closed loop with one caller.  Each step is
one RK4 substage of a pseudo-spectral Navier–Stokes DNS
(``repro.solvers.navier_stokes``): six c2r and three r2c transforms of
the system under test on stacked fields, the cross product, and the
fused k-space update, as one program with the state donated, ending in
``block_until_ready``.  The substage index cycles 0..3, so every four
steps make one RK4 time step, and the chain carries on from the
configuration's initial field for the seed (``initial_field`` of its
module), made in set-up: nothing is generated in the window.

The program's transforms take a stack of fields in one call (its packed
r2c plan takes leading batch axes); the plain reference in its place
takes one field, and is mapped over the stack.

Checked once the window has closed, from the chain's last velocity U:

- ``div_err``: max |k . U| over max |k| |U|.  Each substage projects its
  increment, so a fault in any substage of the chain leaves a
  divergence here.
- ``increment_err``: the same compiled substage, run once more from U
  with zero U0 and U1 at substage 1, returns b dt dU as its U, a dt dU
  as its U1 and zero as its U0: the increments themselves, to float32's
  relative precision (from the chain's own U0 they would be rounded
  against |U0|, about 1e-4 of an increment at this dt).  The widest gap
  of each from the reference's increment (``reference_rhs`` of the
  configuration's module, at ``Precision.HIGHEST``), over the widest
  reference increment of that output.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench import compare, system
# the program's solver: a checkout without it fails here, at set-up
from repro.solvers import navier_stokes

NUMBERS = ("increment_err", "div_err")

#: the substage the check runs again: neither the first (which would
#: copy U into U0 and U1) nor the last (which would take U from U1),
#: with its RK4 coefficients a and b (Mortensen & Langtangen 2016)
CHECK_RK, CHECK_A, CHECK_B = 1, 1 / 3, 1 / 2


class Step:
    def __init__(self, system_, cfg: dict, traffic_cfg: dict, seed: int,
                 cfg_module=None):
        if cfg["problem"] != "r2c":
            raise ValueError("ns_rk_stage drives an r2c plan")
        self.sys = system_
        self.cfg = cfg
        self.cfg_module = cfg_module
        self.traffic = traffic_cfg
        self.seed = seed
        self.shape = tuple(cfg["shape"])
        self.state = None
        self.rk = 0

    def _transforms(self):
        fwd, inv = self.sys.forward, self.sys.inverse
        if isinstance(self.sys, system.Program):
            return fwd, inv
        return (lambda v: jax.lax.map(fwd, v)), (lambda v: jax.lax.map(inv, v))

    def setup(self) -> None:
        fwd, inv = self._transforms()
        ns = self.cfg["ns"]
        self.solver = navier_stokes.NavierStokes(
            fwd, inv, self.shape, nu=float(ns["nu"]), dt=float(ns["dt"]))
        self.rks = [jax.device_put(jnp.int32(rk)) for rk in range(4)]
        state = jax.block_until_ready(self.solver.start(
            self.cfg_module.initial_field(self.cfg, self.seed,
                                          self.traffic.get("input"))))
        for _ in range(int(self.traffic.get("warmup", 1))):
            state = self.solver.substage(state, self.rks[self.rk])
            self.rk = (self.rk + 1) % 4
        self.state = jax.block_until_ready(state)

    def window(self, seconds: float) -> tuple[int, float]:
        """Steps until ``seconds`` have passed; (steps, elapsed seconds)."""
        substage, rks = self.solver.substage, self.rks
        state, self.state = self.state, None
        rk = self.rk
        steps = 0
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            while True:
                with TraceAnnotation("bench.step"):
                    with TraceAnnotation("bench.call.ns_substage"):
                        state = substage(state, rks[rk])
                    with TraceAnnotation("bench.wait"):
                        jax.block_until_ready(state)
                rk = (rk + 1) % 4
                steps += 1
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
        self.state, self.rk = state, rk
        return steps, elapsed

    def release(self) -> None:
        self.sys.release()

    def check(self) -> dict:
        u_hat = self.state[0]
        self.state = None
        div = self.cfg_module.divergence(self.cfg, u_hat)
        host = jax.device_get(u_hat)
        zero = jnp.zeros_like(u_hat)
        out = self.solver.substage((u_hat, zero, jnp.zeros_like(u_hat)),
                                   self.rks[CHECK_RK])
        del u_hat, zero
        got_u, got_u0, got_u1 = jax.device_get(out)
        del out
        self.solver = None
        du = self.cfg_module.reference_rhs(self.cfg, jnp.asarray(host))
        del host
        dt = float(self.cfg["ns"]["dt"])
        a, b = CHECK_A * dt, CHECK_B * dt
        gaps = [compare.rel_gap(jnp.asarray(got_u), b * du),
                compare.rel_gap(jnp.asarray(got_u1), a * du)]
        widest = float(jnp.max(jnp.abs(b * du)))
        gaps.append(float(jnp.max(jnp.abs(jnp.asarray(got_u0)))) / widest
                    if widest > 0 else float("inf"))
        return {"increment_err": max(gaps), "div_err": div}
