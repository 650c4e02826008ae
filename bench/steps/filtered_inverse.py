"""Step kind ``filtered_inverse``: a closed loop with one caller.  Each step
runs ``forward_filtered(grid, filter)``, then ``inverse``, and ends in
``block_until_ready``, as an MD integrator needs the reciprocal-space
potential before its next step.  Step i takes grid ``i mod pool`` of a
pool made from the seed in set-up, so no step repeats its neighbour's
input and nothing is generated in the window.  The filter comes from the
configuration's module (``kspace_filter``), made once in set-up.

Checked once the window has closed: ``potential_err``, the widest gap of
a sample of the window's outputs (``sample`` of them, drawn from the seed
by reservoir sampling over all steps) against the reference's filtered
round trip of the same grid, over the widest reference value.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench import compare, reference, traffic

NUMBERS = ("potential_err",)


class Step:
    def __init__(self, system, cfg: dict, traffic_cfg: dict, seed: int,
                 cfg_module=None):
        if cfg["problem"] != "r2c":
            raise ValueError("filtered_inverse drives an r2c plan")
        self.sys = system
        self.cfg = cfg
        self.cfg_module = cfg_module
        self.traffic = traffic_cfg
        self.seed = seed
        self.shape = tuple(cfg["shape"])
        self.samples = []

    def setup(self) -> None:
        h = self.cfg_module.kspace_filter(self.cfg)
        self.h = jax.device_put(jnp.asarray(h, jnp.complex64),
                                self.sys.output_sharding)
        self.pool = traffic.fields(self.seed, int(self.traffic["pool"]),
                                   self.shape, "float32",
                                   self.sys.input_sharding,
                                   self.traffic.get("input"))
        for x in self.pool[:int(self.traffic.get("warmup", 2))]:
            jax.block_until_ready(
                self.sys.inverse(self.sys.forward_filtered(x, self.h)))

    def window(self, seconds: float) -> tuple[int, float]:
        """Steps until ``seconds`` have passed; (steps, elapsed seconds)."""
        ff, inv, h, pool = (self.sys.forward_filtered, self.sys.inverse,
                            self.h, self.pool)
        keep = int(self.traffic["sample"])
        rng = traffic.host_rng(self.seed)
        samples = []
        steps = 0
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            while True:
                with TraceAnnotation("bench.step"):
                    with TraceAnnotation("bench.call.forward_filtered"):
                        s = ff(pool[steps % len(pool)], h)
                    with TraceAnnotation("bench.call.inverse"):
                        phi = inv(s)
                    with TraceAnnotation("bench.wait"):
                        phi.block_until_ready()
                # reservoir sampling: every step is kept with the same
                # chance, whatever the number of steps turns out to be
                if steps < keep:
                    samples.append((steps, phi))
                else:
                    j = int(rng.integers(0, steps + 1))
                    if j < keep:
                        samples[j] = (steps, phi)
                steps += 1
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
        self.samples = samples
        return steps, elapsed

    def release(self) -> None:
        self.sys.release()

    def check(self) -> dict:
        ref = reference.jitted("filtered_round_trip", "highest",
                               nz=self.shape[-1])
        worst = 0.0
        for i, phi in self.samples:
            want = ref(self.pool[i % len(self.pool)], self.h)
            worst = max(worst, compare.rel_gap(phi, want))
        self.samples = []
        return {"potential_err": worst}
