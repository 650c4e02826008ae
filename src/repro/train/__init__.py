"""Training/serving substrate: optimizer, steps, data, checkpoints."""

from repro.train.optimizer import OptConfig, adamw_update, init_opt_state
from repro.train.train_step import (init_train_state, loss_fn,
                                    make_serve_steps, make_shard_ctx,
                                    make_train_step)

__all__ = ["OptConfig", "adamw_update", "init_opt_state", "init_train_state",
           "loss_fn", "make_serve_steps", "make_shard_ctx", "make_train_step"]
