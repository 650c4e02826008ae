import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first
# init.  512 placeholder host devices back the production meshes below.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) cell and for the paper's FFT grids,
``jax.jit(step).lower(**input_specs).compile()`` must succeed on the
single-pod 16x16 mesh AND the 2x16x16 multi-pod mesh.  The compiled
artifact yields ``memory_analysis()`` (fits?) and ``cost_analysis()``
(FLOPs/bytes), and its HLO is parsed for collective bytes — the inputs to
EXPERIMENTS.md §Dry-run and §Roofline.

Results are cached as JSON per cell under ``--out`` (re-runs skip finished
cells), because a 512-partition compile of a 60-layer MoE on one CPU core
is minutes, not seconds.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh both --fft
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import math
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCHS, ASSIGNED, FFT_SHAPES, SHAPES, get_config,
                           shape_supported)
from repro.configs.croft_fft import CroftConfig, croft_1024, croft_128, croft_4096
from repro.core import Croft3D, Decomposition
from repro.core.distributed import FFTOptions
from repro.launch import roofline as rl
from repro.launch.mesh import fft_mesh_axes, make_production_mesh
from repro.models import model as model_lib
from repro.parallel import sharding as sh
from repro.train import train_step as ts
from repro.train.optimizer import OptConfig, init_opt_state


def _sds(shape, dtype, mesh=None, spec=None):
    sharding = NamedSharding(mesh, spec) if mesh is not None else None
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_sds(abstract_tree, spec_tree, mesh):
    return jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, mesh, s), abstract_tree,
        spec_tree, is_leaf=lambda x: isinstance(x, P))


def input_specs(cfg, shape, mesh, multi_pod: bool):
    """ShapeDtypeStruct stand-ins for every model input of one cell:
    weak-type-correct, shardable, zero allocation."""
    axes = sh.MeshAxes(pod="pod" if multi_pod else None)
    dp = axes.dp_axes
    dp_size = math.prod(mesh.shape[a] for a in dp)
    gb = shape.global_batch
    batch_spec = dp if gb % dp_size == 0 else None
    if isinstance(batch_spec, tuple) and len(batch_spec) == 1:
        batch_spec = batch_spec[0]

    out = {}
    if shape.kind == "train":
        out["tokens"] = _sds((gb, shape.seq_len + 1), jnp.int32, mesh,
                             P(batch_spec, None))
    elif shape.kind == "prefill":
        out["tokens"] = _sds((gb, shape.seq_len), jnp.int32, mesh,
                             P(batch_spec, None))
    else:  # decode
        out["tokens"] = _sds((gb, 1), jnp.int32, mesh, P(batch_spec, None))
    if cfg.encoder is not None:
        out["frames"] = _sds((gb, cfg.n_frontend_tokens, cfg.d_model),
                             jnp.float32, mesh, P(batch_spec, None, None))
    elif cfg.frontend == "vision":
        out["prefix_embeds"] = _sds(
            (gb, cfg.n_frontend_tokens, cfg.d_model), jnp.float32, mesh,
            P(batch_spec, None, None))
    return out, batch_spec


def model_flops_for(cfg, shape) -> float:
    n = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token/seq


# --------------------------------------------------------------------------
# LM cells
# --------------------------------------------------------------------------

def lower_lm_cell(arch: str, shape_name: str, multi_pod: bool,
                  kv_block: int = 0, opts: dict | None = None) -> dict:
    opts = opts or {}
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if kv_block <= 0:
        # §Perf: single-block attention for 4k training (no scan stacking);
        # prefill keeps 2k blocks (score memory scales Sq_loc x kv_block)
        kv_block = shape.seq_len if shape.kind == "train" else 2048
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return {"status": "skip", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = math.prod(mesh.devices.shape)
    axes = sh.MeshAxes(pod="pod" if multi_pod else None)

    abstract_params = jax.eval_shape(
        lambda k: model_lib.init_params(k, cfg), jax.random.key(0))
    pspecs = sh.param_specs(abstract_params, mesh, axes)
    params_sds = _tree_sds(abstract_params, pspecs, mesh)
    inputs, batch_spec = input_specs(cfg, shape, mesh, multi_pod)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = OptConfig(
                moment_dtype=opts.get("moment_dtype", "bfloat16"))
            abstract_opt = jax.eval_shape(
                lambda p: init_opt_state(p, opt_cfg), abstract_params)
            opt_sds = {
                "m": _tree_sds(abstract_opt["m"], pspecs, mesh),
                "v": _tree_sds(abstract_opt["v"], pspecs, mesh),
                "step": jax.ShapeDtypeStruct((), jnp.int32),
            }
            step_fn = ts.make_train_step(
                cfg, opt_cfg, mesh, shape.global_batch, multi_pod=multi_pod,
                kv_block=kv_block, donate=False,
                remat_policy=opts.get("remat_policy", "nothing"))
            lowered = step_fn.lower({"params": params_sds, "opt": opt_sds},
                                    inputs)
        else:
            max_len = shape.seq_len
            abstract_caches = jax.eval_shape(
                lambda: model_lib.init_caches(
                    cfg, shape.global_batch, max_len,
                    enc_len=cfg.n_frontend_tokens if cfg.encoder else 0,
                    dtype=jnp.bfloat16))
            cspecs = sh.cache_specs(abstract_caches, mesh, axes)
            caches_sds = _tree_sds(abstract_caches, cspecs, mesh)
            prefill_fn, decode_fn = ts.make_serve_steps(
                cfg, mesh, shape.global_batch, max_len, multi_pod=multi_pod,
                kv_block=kv_block)
            tok = inputs.pop("tokens")
            if shape.kind == "prefill":
                lowered = prefill_fn.lower(params_sds, tok, caches_sds,
                                           **inputs)
            else:
                lowered = decode_fn.lower(params_sds, tok, caches_sds,
                                          shape.seq_len - 1)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    terms, coll, mem = rl.terms_from_compiled(
        compiled, n_dev, rl.TARGET_DEVICE_KIND, model_flops_for(cfg, shape))
    return {
        "status": "ok", "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "roofline": terms.to_dict(), "collectives": coll, "memory": mem,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "options": opts,
    }


# --------------------------------------------------------------------------
# FFT cells (the paper's own workload)
# --------------------------------------------------------------------------

def lower_fft_cell(grid_name: str, multi_pod: bool,
                   decomposition: str = "pencil",
                   opts: FFTOptions = FFTOptions()) -> dict:
    fshape = FFT_SHAPES[grid_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = math.prod(mesh.devices.shape)
    if decomposition == "pencil":
        axes = fft_mesh_axes(mesh)
        decomp = Decomposition("pencil", axes)
    elif decomposition == "slab":
        names = mesh.axis_names
        decomp = Decomposition("slab", (tuple(names),))
    else:
        names = mesh.axis_names  # cell needs 3 axes: only multi-pod mesh
        if len(names) != 3:
            return {"status": "skip", "reason": "cell needs a 3-axis mesh"}
        decomp = Decomposition("cell", tuple(names))
    try:
        plan = Croft3D(fshape.grid, mesh, decomp, opts,
                       dtype=jnp.dtype(fshape.dtype))
    except ValueError as e:
        return {"status": "skip", "reason": str(e)}
    t0 = time.time()
    lowered = plan.lower_forward()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    terms, coll, mem = rl.terms_from_compiled(
        compiled, n_dev, rl.TARGET_DEVICE_KIND, plan.flops_model())
    return {
        "status": "ok", "arch": f"croft-{decomposition}",
        "shape": grid_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "n_devices": n_dev,
        "compile_s": round(t_compile, 1),
        "roofline": terms.to_dict(), "collectives": coll, "memory": mem,
        "comm_model_bytes": plan.comm_bytes_model(),
        "options": dataclasses_asdict(opts),
    }


def dataclasses_asdict(o):
    import dataclasses
    return dataclasses.asdict(o)


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def run_cell(name: str, fn, out_dir: str, force: bool) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "error":  # errors always retry
            print(f"[cached] {name}: {rec.get('status')}")
            return rec
    print(f"[run]    {name} ...", flush=True)
    try:
        rec = fn()
    except Exception as e:
        rec = {"status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    finally:
        jax.clear_caches()  # keep 80-cell runs from accumulating executables
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec.get("status")
    extra = ""
    if status == "ok":
        r = rec["roofline"]
        extra = (f" compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s"
                 f" coll={r['collective_s']:.4f}s -> {r['bottleneck']}"
                 f" (compile {rec.get('compile_s', '?')}s)")
    elif status == "error":
        extra = " " + rec["error"][:160]
    elif status == "skip":
        extra = " " + rec.get("reason", "")[:120]
    print(f"[done]   {name}: {status}{extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch or 'all'")
    ap.add_argument("--shape", default=None, help="one shape or 'all'")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--fft", action="store_true", help="run FFT cells")
    ap.add_argument("--fft-grid", default="fft_1024")
    ap.add_argument("--fft-decomp", default="pencil")
    ap.add_argument("--all", action="store_true",
                    help="entire 40-cell LM matrix + FFT cells")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--kv-block", type=int, default=0,
                    help="0 = per-shape heuristic")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    records = []

    if args.fft or args.all:
        grids = list(FFT_SHAPES) if args.all else [args.fft_grid]
        decomps = (["pencil", "slab"] if args.all else [args.fft_decomp])
        for mp in meshes:
            for g in grids:
                for dec in decomps:
                    tag = f"fft-{g}-{dec}-{'mp' if mp else 'sp'}"
                    records.append(run_cell(
                        tag, lambda g=g, dec=dec, mp=mp: lower_fft_cell(
                            g, mp, dec), args.out, args.force))

    archs = []
    if args.all:
        archs = list(ASSIGNED)
    elif args.arch:
        archs = list(ASSIGNED) if args.arch == "all" else [args.arch]
    shapes = []
    if args.all:
        shapes = list(SHAPES)
    elif args.shape:
        shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    if archs and not shapes:
        shapes = list(SHAPES)
    if shapes and not archs:
        archs = list(ASSIGNED)

    for mp in meshes:
        for a in archs:
            for s in shapes:
                tag = f"{a}-{s}-{'mp' if mp else 'sp'}"
                records.append(run_cell(
                    tag, lambda a=a, s=s, mp=mp: lower_lm_cell(
                        a, s, mp, kv_block=args.kv_block),
                    args.out, args.force))

    n_ok = sum(r.get("status") == "ok" for r in records)
    n_skip = sum(r.get("status") == "skip" for r in records)
    n_err = sum(r.get("status") == "error" for r in records)
    print(f"\n=== dry-run summary: {n_ok} ok, {n_skip} skip, {n_err} error ===")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
