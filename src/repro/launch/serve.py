"""Serving driver for the transform service (and the legacy LM loop).

Default mode drives :class:`repro.serve.TransformService` with a
synthetic open-loop request stream and prints latency / occupancy /
plan-cache stats — the operational entry point for ROADMAP item 2:

``python -m repro.launch.serve --shape 32,32,32 --problem mix
--requests 64 --qps 50 --wisdom wisdom.json``

Passing ``--arch`` selects the legacy LM prefill+decode loop instead:

``python -m repro.launch.serve --arch rwkv6-3b --smoke --prompt-len 32
--gen-len 32 --batch 4``
"""

from __future__ import annotations

import argparse
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh


# -- transform-service mode (default) ---------------------------------------

def _mesh_for_transforms():
    """Pencil mesh over whatever devices exist; None = single device
    (the service then runs meshless local plans)."""
    n = len(jax.devices())
    if n < 2:
        return None
    py = int(math.sqrt(n))
    while n % py:
        py -= 1
    return make_mesh((py, n // py), ("y", "z"))


def transforms_main(args) -> None:
    from repro.serve import TransformService

    mesh = _mesh_for_transforms()
    shape = tuple(int(s) for s in args.shape.split(","))
    if len(shape) != 3:
        raise SystemExit(f"--shape must be 3-D, got {shape}")
    print(f"mesh: {dict(mesh.shape) if mesh else 'single-device'}  "
          f"shape: {shape}  problem: {args.problem}")

    rng = np.random.RandomState(args.seed)
    cplx = (rng.randn(*shape) + 1j * rng.randn(*shape)).astype(np.complex64)
    real = rng.randn(*shape).astype(np.float32)
    filt = rng.randn(*shape).astype(np.complex64)
    workload = {
        "c2c": [(cplx, {})],
        "r2c": [(real, {"problem": "r2c"})],
        "filtered": [(cplx, {"problem": "filtered", "h": filt})],
    }
    reqs = (workload["c2c"] * 3 + workload["r2c"] * 2
            + workload["filtered"]) if args.problem == "mix" \
        else workload[args.problem]

    svc = TransformService(
        mesh, max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        wisdom_path=args.wisdom, measure_after=args.measure_after)
    with svc:
        t0 = time.monotonic()
        futs = []
        for i in range(args.requests):
            if args.qps > 0:
                delay = t0 + i / args.qps - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
            x, kw = reqs[i % len(reqs)]
            futs.append(svc.submit(x, **kw))
        results = [f.result(timeout=600) for f in futs]
        bad = [r for r in results if not r.ok]
        if bad:
            raise SystemExit(f"{len(bad)} requests failed; first error: "
                             f"{bad[0].error}")
        stats = svc.stats()

    lat = stats["latency_ms"]
    print(f"served {stats['requests']} requests in "
          f"{stats['batches']} batches "
          f"(mean batch {stats['mean_batch']:.2f}, "
          f"occupancy {stats['occupancy']:.0%})")
    print(f"latency ms: p50={lat['p50']:.2f} p90={lat['p90']:.2f} "
          f"p99={lat['p99']:.2f}")
    cache = stats["plan_cache"]
    print(f"plan cache: {cache['stats']}  states: "
          f"{ {k.split('|')[0] + '|' + k.split('|')[-1]: v['state'] for k, v in cache['plans'].items()} }")


# -- legacy LM prefill/decode loop (``--arch``) -----------------------------

def lm_main(args) -> None:
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.models import init_caches, init_params
    from repro.train import make_serve_steps
    from repro.train.data import synth_tokens
    from repro.train.train_step import temperature_sample

    cfg = get_config(args.arch, smoke=args.smoke)
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode step")
    mesh = make_local_mesh()
    params = init_params(jax.random.PRNGKey(args.seed), cfg)

    max_len = args.prompt_len + args.gen_len \
        + (cfg.n_frontend_tokens if cfg.prefix_lm else 0)
    prefill_fn, decode_fn = make_serve_steps(
        cfg, mesh, args.batch, max_len, kv_block=args.kv_block)

    prompts = synth_tokens(args.seed, 0, args.batch, args.prompt_len,
                           cfg.vocab)
    enc_len = cfg.n_frontend_tokens if cfg.encoder is not None else 0
    caches = init_caches(cfg, args.batch, max_len, enc_len=enc_len,
                         dtype=jnp.bfloat16)
    kwargs = {}
    rng = np.random.default_rng(args.seed)
    if cfg.encoder is not None:
        kwargs["frames"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_frontend_tokens, cfg.d_model), np.float32))
    elif cfg.frontend == "vision":
        kwargs["prefix_embeds"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_frontend_tokens, cfg.d_model), np.float32))

    with jax.set_mesh(mesh):
        t0 = time.monotonic()
        logits, caches = prefill_fn(params, jnp.asarray(prompts), caches,
                                    **kwargs)
        logits.block_until_ready()
        t_prefill = time.monotonic() - t0
        key = jax.random.PRNGKey(args.seed)
        tok = temperature_sample(key, logits, args.temperature)[:, None]
        out = [tok]
        prefix = cfg.n_frontend_tokens if cfg.prefix_lm else 0
        t0 = time.monotonic()
        for i in range(args.gen_len - 1):
            t = prefix + args.prompt_len + i
            logits, caches = decode_fn(params, tok, caches, t)
            key, sub = jax.random.split(key)
            tok = temperature_sample(sub, logits, args.temperature)[:, None]
            out.append(tok)
        jax.block_until_ready(out[-1])
        t_decode = time.monotonic() - t0

    gen = np.concatenate([np.asarray(t) for t in out], axis=1)
    tps = args.batch * (args.gen_len - 1) / max(t_decode, 1e-9)
    print(f"prefill: {t_prefill:.3f}s for {args.batch}x{args.prompt_len} tok")
    print(f"decode : {t_decode:.3f}s for {args.gen_len-1} steps "
          f"({tps:.1f} tok/s)")
    print(f"sample generations (first 16 ids):\n{gen[:, :16]}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    # transform-service mode
    ap.add_argument("--shape", default="32,32,32",
                    help="3-D transform shape, e.g. 64,64,64")
    ap.add_argument("--problem", default="mix",
                    choices=("c2c", "r2c", "filtered", "mix"))
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="offered request rate; 0 = as fast as possible")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--wisdom", default=None,
                    help="wisdom file: cold starts read it, background "
                         "measure upgrades merge into it")
    ap.add_argument("--measure-after", type=int, default=None,
                    help="dispatches of a key before the background "
                         "measure-mode upgrade")
    # legacy LM mode
    ap.add_argument("--arch", default=None,
                    help="run the legacy LM prefill/decode loop instead")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-block", type=int, default=512)
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.arch:
        lm_main(args)
    else:
        transforms_main(args)


if __name__ == "__main__":
    main()
