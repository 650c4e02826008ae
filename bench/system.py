"""The system under test, built from a configuration file, and the plain
reference put in its place (the control).

Both expose the same calls: ``forward``, ``inverse``, ``forward_filtered``,
the input and output shardings, and ``release``.  A step kind
(``bench/steps``) drives either one without knowing which it has.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import reference


def _spec(layout) -> tuple | None:
    return None if layout is None else tuple(layout)


class Program:
    """The program's ``Croft3D`` plan as the configuration states it."""

    def __init__(self, cfg: dict, devices):
        from repro.core import Croft3D, Decomposition, FFTOptions
        from repro.launch.mesh import make_mesh

        self.cfg = cfg
        self.mesh = None
        decomp = None
        if cfg.get("mesh"):
            m = cfg["mesh"]
            n = math.prod(m["shape"])
            self.mesh = make_mesh(tuple(m["shape"]), tuple(m["axes"]),
                                  devices=list(devices)[:n])
            d = cfg["decomposition"]
            decomp = Decomposition(d["kind"], tuple(d["axes"]))
        self.plan = Croft3D(tuple(cfg["shape"]), self.mesh, decomp,
                            FFTOptions(**cfg.get("options", {})),
                            dtype=jnp.dtype(cfg["dtype"]),
                            problem=cfg["problem"],
                            strategy=cfg.get("strategy"))
        self.input_sharding = self.plan.input_sharding
        self.output_sharding = self.plan.output_sharding

    def forward(self, x):
        return self.plan.forward(x)

    def inverse(self, y):
        return self.plan.inverse(y)

    def forward_filtered(self, x, h):
        return self.plan.forward_filtered(x, h)

    def release(self):
        self.plan.release()


class Reference:
    """The plain reference (``bench/reference.py``) in the program's place,
    at ``precision``: with ``"high"``, the control.  It takes its mesh and
    layouts from the configuration, not from the program."""

    def __init__(self, cfg: dict, devices, precision: str):
        self.cfg = cfg
        self.mesh = None
        self.precision = precision
        layout = cfg.get("layout") or {}
        self.in_spec = _spec(layout.get("input"))
        self.out_spec = _spec(layout.get("output"))
        if cfg.get("mesh"):
            m = cfg["mesh"]
            n = math.prod(m["shape"])
            grid = np.array(list(devices)[:n]).reshape(m["shape"])
            self.mesh = Mesh(grid, tuple(m["axes"]))
        if self.mesh is not None and self.in_spec != self.out_spec:
            raise ValueError("the reference keeps its input's layout; the "
                             "configuration states another for the output")
        self.input_sharding = self._sharding(self.in_spec)
        self.output_sharding = self._sharding(self.out_spec)
        nz = cfg["shape"][-1]
        if cfg["problem"] == "c2c":
            self._fwd = reference.jitted("fft3", precision, self.mesh,
                                         self.in_spec)
            self._inv = reference.jitted("ifft3", precision, self.mesh,
                                         self.in_spec)
        else:
            self._fwd = reference.jitted("rfft3", precision)
            self._inv = reference.jitted("irfft3", precision, nz=nz)
        self._filt = jax.jit(lambda x, h: self._fwd(x) * h)

    def _sharding(self, spec):
        if self.mesh is None or spec is None:
            return None
        return NamedSharding(self.mesh, P(*spec))

    def forward(self, x):
        return self._fwd(x)

    def inverse(self, y):
        return self._inv(y)

    def forward_filtered(self, x, h):
        return self._filt(x, h)

    def release(self):
        pass
