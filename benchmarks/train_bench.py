"""Training-step benchmark: the differentiable distributed transform.

Two sections:

* **Spectral training workload** (both modes, the CI gate): a learned
  spectral filter — real-space gate + k-space filter around the packed
  r2c pipeline (``repro.models.spectral``) — trained with plain SGD on
  an 8-virtual-device pencil mesh in a subprocess.  Gradients flow
  through ``repro.grad``'s adjoint schedules, not XLA collective
  autodiff.  Writes ``BENCH_train.json`` with deterministic gates:

    - ``loss_monotone`` / ``loss_halved``: the smoke run's loss must
      strictly decrease and at least halve (the run is seeded, so this
      is deterministic, not a flaky convergence bet);
    - ``grad_vs_numerical_max_rel``: analytic grads vs central finite
      differences (the loss is quadratic along any single-coordinate
      line, so central differences are *exact* up to float32 rounding);
    - ``grad_packed_vs_embed_rel``: the packed pipeline's custom-VJP
      grads vs the embed strategy (XLA autodiff over the Hermitian glue
      composed with the c2c core's adjoint) — two independent gradient
      routes through different code;
    - ``hlo_mirror``: for the c2c core (alltoall both layouts, ring,
      pairwise) the backward pass must compile to *exactly* the forward
      schedule's per-type collective counts, and the all-to-all count
      must equal the adjoint schedule's per-stage prediction
      (``per_stage_costs`` ``k_eff`` — one launch per K-chunk), straight
      from the same IR the executor runs.  The packed r2c counts are
      recorded unequal-by-design: the DC/Nyquist plane unfold reflects
      across *sharded* kx/ky axes, so its transpose adds a few
      plane-sized permutes the forward does not have.

* **LM step times** (full mode only): the original smoke-config
  train-step wall rows plus modeled production step times from the
  dry-run artifacts.

``python -m benchmarks.train_bench --smoke`` is the CI entry point.
"""

from __future__ import annotations

import argparse
import os

from benchmarks.common import (REPO, emit, load_dryrun, run_subprocess_bench,
                               time_fn)

BENCH_JSON = os.path.join(REPO, "BENCH_train.json")

_SPECTRAL_CODE = """
import json, time, numpy as np, jax, jax.numpy as jnp
from repro.core import Croft3D, Decomposition, FFTOptions
from repro.launch import hlo_cost
from repro.models.spectral import (init_spectral_filter_params,
                                   place_spectral_filter_params,
                                   spectral_filter_apply)
from repro.train import make_spectral_train_step, spectral_loss_fn
from repro.tuning import Candidate, per_stage_costs
from repro.launch.mesh import make_mesh

N = {n}
steps = {steps}
shape = (N, N, N)
mesh = make_mesh((4, 2), ("y", "x"))
dec = Decomposition("pencil", ("y", "x"))
sizes = dict(mesh.shape)
report = {{"shape": list(shape), "mesh": sizes,
           "backend": jax.default_backend(), "gates": {{}}, "hlo": {{}}}}

def collective_counts(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return {{k: int(v["count"])
             for k, v in hlo_cost.analyze(txt).collectives.items()}}

# ---- training loop: learned spectral filter over the packed r2c plan ----
plan = Croft3D(shape, mesh, dec, FFTOptions(), problem="r2c",
               strategy="packed")
rng = np.random.RandomState(0)
x = jax.device_put(jnp.asarray(rng.randn(*shape), plan.input_dtype),
                   plan.input_sharding)
true = place_spectral_filter_params(plan, {{
    "gate": jnp.asarray(1.0 + 0.3 * rng.randn(*shape), jnp.float32),
    "filter": jnp.asarray(1.0 + 0.3 * rng.randn(*plan.spectrum_shape),
                          jnp.float32)}})
target = spectral_filter_apply(plan, true, x)
step, loss_fn = make_spectral_train_step(plan, lr=0.05)
params = place_spectral_filter_params(
    plan, init_spectral_filter_params(jax.random.PRNGKey(1), plan))

losses, wall0 = [], None
for i in range(steps):
    params, loss = step(params, x, target)
    losses.append(float(loss))  # float() syncs, so the wall below is honest
    if i == 0:
        wall0 = time.perf_counter()  # step 0 paid compilation
wall = (time.perf_counter() - wall0) / max(1, steps - 1)
report["losses"] = losses
report["step_wall_s"] = wall
gate_mono = all(b < a for a, b in zip(losses, losses[1:]))
gate_conv = losses[-1] < 0.5 * losses[0]
report["gates"]["loss_monotone"] = gate_mono
report["gates"]["loss_halved"] = gate_conv
if not (gate_mono and gate_conv):
    raise SystemExit(f"REGRESSION: spectral training loss not decreasing "
                     f"over the seeded smoke run: {{losses}}")
print(f"ROW,train/spectral-step/{{N}}^3,{{wall * 1e6:.3f}},0")

# ---- oracle 1: grads vs central finite differences ----------------------
g = jax.jit(jax.grad(loss_fn))(params, x, target)
fd_max_rel = 0.0
for field in ("gate", "filter"):
    for ij in [(1, 2, 3), (0, 0, 0), (3, 1, 2)]:
        eps = 0.5  # loss is quadratic along this line: central diff exact
        def loss_at(v, field=field, ij=ij):
            pp = dict(params)
            pp[field] = params[field].at[ij].add(v)
            return float(loss_fn(pp, x, target))
        fd = (loss_at(eps) - loss_at(-eps)) / (2 * eps)
        an = float(g[field][ij])
        rel = abs(fd - an) / max(abs(fd), abs(an), 1e-6)
        fd_max_rel = max(fd_max_rel, rel)
report["gates"]["grad_vs_numerical_max_rel"] = fd_max_rel
if fd_max_rel > 1e-2:
    raise SystemExit(f"REGRESSION: analytic gradient {{fd_max_rel:.2e}} "
                     "rel off the finite-difference oracle (gate 1e-2)")

# ---- oracle 2: packed custom-VJP grads vs the embed strategy ------------
embed = Croft3D(shape, mesh, dec, FFTOptions(), problem="r2c",
                strategy="embed")
xe = jax.device_put(x, embed.input_sharding)
ge = jax.jit(jax.grad(
    lambda p, v, t: spectral_loss_fn(embed, p, v, t)))(params, xe, target)
embed_rels = {{}}
for field in ("gate", "filter"):
    num = float(jnp.linalg.norm(g[field] - ge[field]))
    den = float(jnp.linalg.norm(g[field])) or 1.0
    embed_rels[field] = num / den
report["gates"]["grad_packed_vs_embed_rel"] = embed_rels
if max(embed_rels.values()) > 1e-4:
    raise SystemExit(f"REGRESSION: packed-vs-embed gradient routes "
                     f"disagree: {{embed_rels}} (gate 1e-4)")

# ---- gate 3: backward HLO mirrors the adjoint schedule ------------------
mirror_ok = True
for tag, opts in {{
    "c2c-alltoall-natural": FFTOptions(),
    "c2c-alltoall-spectral": FFTOptions(output_layout="spectral"),
    "c2c-ring": FFTOptions(output_layout="spectral", transpose_impl="ring"),
    "c2c-pairwise": FFTOptions(output_layout="spectral",
                               transpose_impl="pairwise"),
}}.items():
    cplan = Croft3D(shape, mesh, dec, opts)
    xc = jax.device_put(jnp.zeros(shape, jnp.complex64),
                        cplan.input_sharding)
    fwd_counts = collective_counts(cplan._fwd, xc)
    y, pull = jax.vjp(cplan._fwd, xc)
    bwd_counts = collective_counts(pull, jnp.ones_like(y))
    rec = {{"fwd": fwd_counts, "bwd": bwd_counts,
            "mirror": bwd_counts == fwd_counts}}
    if opts.transpose_impl == "alltoall":
        rows = per_stage_costs(shape, Candidate(dec, opts,
                                                problem="c2c_grad"),
                               sizes, jnp.complex64)
        pred = sum(int(r["k_eff"]) for r in rows
                   if r["direction"] == "bwd" and r["collective_s"] > 0)
        rec["predicted_bwd_all_to_all"] = pred
        rec["prediction_match"] = pred == bwd_counts.get("all-to-all", 0)
        mirror_ok = mirror_ok and rec["prediction_match"]
    mirror_ok = mirror_ok and rec["mirror"]
    report["hlo"][tag] = rec
# recorded, not equality-gated: the packed pipeline's DC/Nyquist unfold
# reflects across sharded kx/ky axes, so its transpose adds plane-sized
# permutes (see module docstring)
yp, pullp = jax.vjp(plan._fwd, x)
report["hlo"]["r2c-packed"] = {{
    "fwd": collective_counts(plan._fwd, x),
    "bwd": collective_counts(pullp, jnp.ones_like(yp))}}
report["gates"]["hlo_mirror"] = mirror_ok
if not mirror_ok:
    raise SystemExit("REGRESSION: backward HLO collective counts do not "
                     f"mirror the adjoint schedule: {{report['hlo']}}")

with open({out!r}, "w") as f:
    json.dump(report, f, indent=1, sort_keys=True)
print("JSON_WRITTEN")
"""


def _run_spectral(smoke: bool) -> None:
    code = _SPECTRAL_CODE.format(n=16 if smoke else 32,
                                 steps=10 if smoke else 20, out=BENCH_JSON)
    out = run_subprocess_bench(code, n_devices=8, timeout=1200)
    for line in out.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",")
            emit(name, float(us), bool(int(derived)))
    if "JSON_WRITTEN" not in out:
        raise RuntimeError("spectral train sweep did not write "
                           "BENCH_train.json")


def _run_lm() -> None:
    import jax

    from repro.configs import get_config
    from repro.train import OptConfig, init_train_state, make_train_step
    from repro.train.data import SyntheticDataset

    for arch in ["yi-9b", "rwkv6-3b"]:
        cfg = get_config(arch, smoke=True)
        ocfg = OptConfig(lr=1e-3)
        state = init_train_state(jax.random.PRNGKey(0), cfg, ocfg, None)
        step = make_train_step(cfg, ocfg, None, 4, kv_block=32, donate=False)
        ds = SyntheticDataset(cfg.vocab, 64, 4)
        batch = ds.batch_at(0)
        us = time_fn(lambda s, b: step(s, b)[1]["loss"], state, batch,
                     warmup=1, iters=3)
        emit(f"train/smoke-step/{arch}", us, False)

    # production cells: modeled step time from the compiled dry-run
    for cell in ["yi-34b-train_4k-sp", "mixtral-8x22b-train_4k-sp",
                 "deepseek-v2-236b-train_4k-sp", "rwkv6-3b-decode_32k-sp"]:
        rec = load_dryrun(cell)
        if rec:
            emit(f"train/modeled-step/{cell}",
                 rec["roofline"]["step_time_s"] * 1e6, True)


def run(smoke: bool = False) -> None:
    if not smoke:
        _run_lm()
    _run_spectral(smoke)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI run: spectral workload only, 16^3")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
