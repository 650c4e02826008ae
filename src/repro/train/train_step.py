"""Jitted train / serve steps with full distribution plumbing.

``make_train_state`` + ``make_train_step`` give the production path:
fp32 master params (2-D sharded), bf16 compute cast, chunked fused loss,
AdamW, donated state.  ``make_serve_steps`` builds the prefill/decode pair
with sequence-sharded caches (flash-decoding layout).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.models.model import ShardCtx
from repro.parallel import sharding as sh
from repro.parallel.loss import chunked_cross_entropy
from repro.train import optimizer as opt_lib


def make_shard_ctx(mesh: Optional[Mesh], global_batch: int,
                   multi_pod: bool = False) -> Optional[ShardCtx]:
    if mesh is None:
        return None
    axes = sh.MeshAxes(pod="pod" if multi_pod else None)
    dp_axes = axes.dp_axes
    import math
    dp_size = math.prod(mesh.shape[a] for a in dp_axes)
    dp = dp_axes if global_batch % dp_size == 0 else None
    if dp is not None and len(dp) == 1:
        dp = dp[0]
    return ShardCtx(mesh=mesh, dp=dp, cp_axis="model", tp="model")


def cast_to_compute(params, dtype):
    dt = jnp.dtype(dtype)
    return jax.tree.map(
        lambda p: p.astype(dt) if p.dtype == jnp.float32 and p.ndim >= 2
        else p, params)


def loss_fn(params, cfg: ModelConfig, batch, shard: Optional[ShardCtx],
            kv_block: int = 1024, n_loss_chunks: int = 8,
            precast: bool = False, remat_policy: str = "nothing"):
    """batch: {"tokens" (B,S+1) int32, optional "prefix_embeds",
    "frames"}.  Next-token prediction on tokens[:-1] -> tokens[1:].

    ``precast=True``: params are already in the compute dtype — the caller
    differentiates w.r.t. the bf16 copies so gradient reductions run in
    bf16 (halves cross-data grad bytes; §Perf)."""
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    compute_params = params if precast else cast_to_compute(params, cfg.dtype)
    kwargs = {}
    if cfg.encoder is not None:
        kwargs["enc_out"] = model_lib.encode(compute_params, cfg,
                                             batch["frames"], kv_block)
    elif cfg.frontend == "vision":
        kwargs["prefix_embeds"] = batch["prefix_embeds"]
    hidden, _, aux = model_lib.forward(
        compute_params, cfg, inputs, mode="train", kv_block=kv_block,
        shard=shard, return_hidden=True, remat_policy=remat_policy, **kwargs)
    head_w = compute_params["embed"].get("head")
    if head_w is None:
        head_w = compute_params["embed"]["tok"].T
    if shard is None:
        axes = None
    else:
        has_pod = "pod" in shard.mesh.axis_names
        axes = sh.MeshAxes(pod="pod" if has_pod else None)
    loss, metrics = chunked_cross_entropy(
        hidden, labels, head_w, n_chunks=n_loss_chunks, axes=axes,
        softcap=cfg.logit_softcap)
    # Switch-style load-balance auxiliary (zero for non-MoE stacks)
    aux_weight = 0.01
    metrics["aux_loss"] = aux
    return loss + aux_weight * aux, metrics


@dataclasses.dataclass
class TrainState:
    params: Any
    opt: Any

    def tree(self):
        return {"params": self.params, "opt": self.opt}


def init_train_state(key, cfg: ModelConfig, opt_cfg: opt_lib.OptConfig,
                     mesh: Optional[Mesh] = None,
                     axes: Optional[sh.MeshAxes] = None):
    """Initialize params + optimizer state, sharded onto the mesh."""
    if mesh is None:
        params = model_lib.init_params(key, cfg)
        return {"params": params, "opt": opt_lib.init_opt_state(params, opt_cfg)}
    axes = axes or sh.MeshAxes()
    abstract = jax.eval_shape(lambda k: model_lib.init_params(k, cfg), key)
    specs = sh.param_specs(abstract, mesh, axes)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    init_fn = jax.jit(lambda k: model_lib.init_params(k, cfg),
                      out_shardings=shardings)
    with jax.set_mesh(mesh):
        params = init_fn(key)
    opt_state = {
        "m": jax.tree.map(lambda p, s: jax.device_put(
            jnp.zeros(p.shape, jnp.dtype(opt_cfg.moment_dtype)), s),
            params, shardings),
        "v": jax.tree.map(lambda p, s: jax.device_put(
            jnp.zeros(p.shape, jnp.dtype(opt_cfg.moment_dtype)), s),
            params, shardings),
        "step": jnp.zeros((), jnp.int32),
    }
    return {"params": params, "opt": opt_state}


def make_train_step(cfg: ModelConfig, opt_cfg: opt_lib.OptConfig,
                    mesh: Optional[Mesh], global_batch: int,
                    multi_pod: bool = False, kv_block: int = 1024,
                    n_loss_chunks: int = 8, donate: bool = True,
                    remat_policy: str = "nothing"):
    """Returns a jitted (state, batch) -> (state, metrics) step."""
    shard = make_shard_ctx(mesh, global_batch, multi_pod)

    def step(state, batch):
        # differentiate w.r.t. the bf16 compute copies: backward-pass
        # collectives (grad reductions, activation-transpose psums) then
        # run in bf16 instead of f32 (§Perf); masters stay f32 in AdamW
        compute_params = cast_to_compute(state["params"], cfg.dtype)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(compute_params, cfg, batch, shard,
                                   kv_block, n_loss_chunks, precast=True,
                                   remat_policy=remat_policy)
        new_params, new_opt, opt_metrics = opt_lib.adamw_update(
            state["params"], grads, state["opt"], opt_cfg)
        metrics = {**metrics, **opt_metrics, "loss": loss}
        return {"params": new_params, "opt": new_opt}, metrics

    donate_argnums = (0,) if donate else ()
    if mesh is None:
        return jax.jit(step, donate_argnums=donate_argnums)
    return jax.jit(step, donate_argnums=donate_argnums)


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def make_serve_steps(cfg: ModelConfig, mesh: Optional[Mesh],
                     global_batch: int, max_len: int,
                     multi_pod: bool = False, kv_block: int = 1024):
    """(prefill_fn, decode_fn).

    prefill(params, tokens, caches, **frontend) -> (last_logits, caches)
    decode(params, token, caches, t)            -> (logits, caches)
    """
    shard = make_shard_ctx(mesh, global_batch, multi_pod)

    def prefill(params, tokens, caches, prefix_embeds=None, frames=None):
        compute_params = cast_to_compute(params, cfg.dtype)
        kwargs = {}
        if cfg.encoder is not None:
            kwargs["enc_out"] = model_lib.encode(compute_params, cfg, frames,
                                                 kv_block)
        if prefix_embeds is not None:
            kwargs["prefix_embeds"] = prefix_embeds
        logits, caches = model_lib.forward(
            compute_params, cfg, tokens, mode="prefill", caches=caches,
            kv_block=kv_block, shard=shard, **kwargs)
        return logits[:, -1], caches

    def decode(params, token, caches, t):
        """token (B, 1); t = global position (prefix included)."""
        compute_params = cast_to_compute(params, cfg.dtype)
        logits, caches = model_lib.forward(
            compute_params, cfg, token, mode="decode", caches=caches,
            start=t, kv_block=kv_block, shard=shard)
        return logits[:, 0], caches

    return (jax.jit(prefill, donate_argnums=(2,)),
            jax.jit(decode, donate_argnums=(2,)))
