"""Training drivers: the differentiable distributed transform, end to end.

Default workload (``--workload spectral``): a learned spectral filter —
real-space gate + k-space filter around the distributed r2c transform
(``repro.models.spectral``) — trained with SGD.  Gradients replay the
tuned plan's *adjoint schedule* (``repro.grad``), and with more than one
device the plan comes from ``Croft3D.tuned(..., grad=True)``: the
autotuner prices forward + adjoint, so the winning plan is optimal for
the training step, not just inference.

    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/train_lm.py --steps 20

``--workload lm`` keeps the original driver: a ~100M-parameter dense
transformer (``--preset 100m``; ``--preset tiny`` for a CPU smoke run)
trained on synthetic data via the full production path (sharded params,
chunked loss, checkpointing, straggler monitor).

    PYTHONPATH=src python examples/train_lm.py --workload lm --preset tiny
"""

import argparse

from repro.configs import get_config  # noqa: F401  (registry also usable)
from repro.launch import train as train_cli
from repro.models.config import (AttentionSpec, LayerSpec, ModelConfig,
                                 simple_stack)
from repro.launch.compile_cache import use_compile_cache

PRESETS = {
    # ~101M params: 12L d=768 12H swiglu, 32k vocab (GPT-2-small-ish)
    "100m": dict(layers=12, d=768, heads=12, kv=12, ff=3072, vocab=32768,
                 seq=512, batch=8, steps=300),
    "tiny": dict(layers=2, d=64, heads=4, kv=2, ff=128, vocab=256,
                 seq=64, batch=4, steps=30),
}


def build_config(p) -> ModelConfig:
    spec = LayerSpec(
        mixer="attn",
        attn=AttentionSpec(kind="gqa", n_heads=p["heads"],
                           n_kv_heads=p["kv"], head_dim=p["d"] // p["heads"]),
        ffn="swiglu",
    )
    return ModelConfig(
        name="example-lm", family="dense", d_model=p["d"], d_ff=p["ff"],
        vocab=p["vocab"], stages=simple_stack(p["layers"], spec),
    )


def run_spectral(args):
    """Train the learned spectral filter over a grad-tuned plan."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Croft3D, Decomposition, FFTOptions
    from repro.models.spectral import (init_spectral_filter_params,
                                       place_spectral_filter_params,
                                       spectral_filter_apply)
    from repro.train import make_spectral_train_step
    from repro.launch.mesh import make_mesh

    n = args.size
    shape = (n, n, n)
    n_dev = len(jax.devices())
    if n_dev == 1:
        plan = Croft3D(shape, problem="r2c")
        print(f"spectral workload: {shape} single-device")
    else:
        if n_dev % 2 == 0:
            mesh = make_mesh((n_dev // 2, 2), ("y", "x"))
        else:
            mesh = make_mesh((n_dev,), ("y",))
        # grad=True: the planner prices forward + adjoint schedule, so
        # the chosen plan is the best *training step*, not best forward
        plan = Croft3D.tuned(shape, mesh, mode="model", problem="r2c",
                             grad=True)
        print(f"spectral workload: {shape} on {dict(mesh.shape)} — "
              f"{plan.tune_result.summary()}")

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape), plan.input_dtype)
    if plan.mesh is not None:
        x = jax.device_put(x, plan.input_sharding)
    true = place_spectral_filter_params(plan, {
        "gate": jnp.asarray(1.0 + 0.3 * rng.randn(*shape), jnp.float32),
        "filter": jnp.asarray(
            1.0 + 0.3 * rng.randn(*plan.spectrum_shape), jnp.float32)})
    target = spectral_filter_apply(plan, true, x)
    step, _ = make_spectral_train_step(plan, lr=args.lr)
    params = place_spectral_filter_params(
        plan, init_spectral_filter_params(jax.random.PRNGKey(1), plan))
    steps = args.steps or 20
    for i in range(steps):
        params, loss = step(params, x, target)
        if i % max(1, steps // 10) == 0 or i == steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="spectral",
                    choices=("spectral", "lm"))
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--size", type=int, default=32,
                    help="spectral: grid size N (N^3 field)")
    ap.add_argument("--lr", type=float, default=0.05,
                    help="spectral: SGD learning rate")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    use_compile_cache()
    if args.workload == "spectral":
        run_spectral(args)
        return
    p = PRESETS[args.preset]
    cfg = build_config(p)
    print(f"example LM: {cfg.param_count():,} params")

    # register it so the production CLI path drives it unchanged
    import repro.configs as configs
    import sys, types
    mod = types.ModuleType("examples._example_lm")
    mod.full = lambda: cfg
    mod.smoke = lambda: cfg
    sys.modules["examples._example_lm"] = mod
    configs.ARCHS["example-lm"] = "examples._example_lm"

    argv = ["--arch", "example-lm",
            "--steps", str(args.steps or p["steps"]),
            "--global-batch", str(p["batch"]),
            "--seq-len", str(p["seq"]),
            "--log-every", "10"]
    if args.ckpt_dir:
        argv += ["--ckpt-dir", args.ckpt_dir]
    train_cli.main(argv)


if __name__ == "__main__":
    main()
