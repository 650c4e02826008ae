"""Substrate tests: optimizer, data pipeline, checkpointing, loss,
compression math."""

import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.train.optimizer import (OptConfig, adamw_update, init_opt_state,
                                   schedule, global_norm)
from repro.train.data import SyntheticDataset, Prefetcher, synth_tokens
from repro.train.checkpoint import CheckpointManager
from repro.parallel.loss import chunked_cross_entropy
from repro.parallel.compression import (compress_residual, dequantize_int8,
                                        quantize_int8, topk_densify,
                                        topk_sparsify)


# --------------------------------------------------------------------------
# optimizer
# --------------------------------------------------------------------------

def test_adamw_matches_reference(rng):
    cfg = OptConfig(lr=1e-2, warmup_steps=0, decay_steps=10**9,
                    weight_decay=0.0, clip_norm=0.0)
    p = {"w": jnp.asarray(rng.randn(4, 4).astype(np.float32))}
    g = {"w": jnp.asarray(rng.randn(4, 4).astype(np.float32))}
    st = init_opt_state(p, cfg)
    new_p, new_st, _ = adamw_update(p, g, st, cfg)
    # reference
    m = 0.1 * np.asarray(g["w"])
    v = 0.05 * np.asarray(g["w"]) ** 2
    mh, vh = m / 0.1, v / 0.05
    ref = np.asarray(p["w"]) - 1e-2 * mh / (np.sqrt(vh) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]), ref, atol=1e-5)
    assert int(new_st["step"]) == 1


def test_adamw_converges_quadratic():
    cfg = OptConfig(lr=0.1, warmup_steps=0, decay_steps=10**9,
                    weight_decay=0.0)
    p = {"w": jnp.asarray([5.0, -3.0])}
    st = init_opt_state(p, cfg)
    for _ in range(200):
        g = {"w": 2 * p["w"]}
        p, st, _ = adamw_update(p, g, st, cfg)
    assert float(jnp.max(jnp.abs(p["w"]))) < 0.05


def test_weight_decay_masked():
    cfg = OptConfig(lr=1e-2, warmup_steps=0, weight_decay=0.5, clip_norm=0.0)
    p = {"w": jnp.ones((2, 2)), "scale": jnp.ones((2,))}
    g = {"w": jnp.zeros((2, 2)), "scale": jnp.zeros((2,))}
    st = init_opt_state(p, cfg)
    new_p, _, _ = adamw_update(p, g, st, cfg)
    assert float(new_p["w"][0, 0]) < 1.0       # decayed
    assert float(new_p["scale"][0]) == 1.0     # 1-D spared


def test_schedule_warmup_cosine():
    cfg = OptConfig(lr=1.0, warmup_steps=10, decay_steps=110,
                    min_lr_ratio=0.1)
    assert float(schedule(cfg, jnp.asarray(0))) == 0.0
    assert abs(float(schedule(cfg, jnp.asarray(10))) - 1.0) < 1e-6
    assert abs(float(schedule(cfg, jnp.asarray(110))) - 0.1) < 1e-6


def test_grad_clip():
    cfg = OptConfig(lr=0.0, clip_norm=1.0, weight_decay=0.0)
    p = {"w": jnp.zeros((3,))}
    g = {"w": jnp.asarray([3.0, 4.0, 0.0])}
    st = init_opt_state(p, cfg)
    _, _, m = adamw_update(p, g, st, cfg)
    assert abs(float(m["grad_norm"]) - 5.0) < 1e-5
    assert abs(float(m["clip_scale"]) - 0.2) < 1e-5


def test_bf16_moments():
    cfg = OptConfig(moment_dtype="bfloat16")
    p = {"w": jnp.ones((2, 2))}
    st = init_opt_state(p, cfg)
    assert st["m"]["w"].dtype == jnp.bfloat16


# --------------------------------------------------------------------------
# data
# --------------------------------------------------------------------------

def test_data_deterministic():
    a = synth_tokens(7, 3, 4, 16, 1000)
    b = synth_tokens(7, 3, 4, 16, 1000)
    c = synth_tokens(7, 4, 4, 16, 1000)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 1000


def test_data_zipfian_bias():
    t = synth_tokens(0, 0, 64, 256, 10_000)
    assert np.mean(t < 100) > 0.3  # mass concentrated at small ids


def test_data_learnable_structure():
    t = synth_tokens(0, 0, 16, 512, 1000)
    rep = np.mean(t[:, 1:] == t[:, :-1])
    assert rep > 0.15  # injected bigram structure


def test_prefetcher():
    ds = SyntheticDataset(100, 8, 2)
    pf = Prefetcher(iter(ds), depth=2)
    batches = [next(pf) for _ in range(3)]
    assert all(b["tokens"].shape == (2, 9) for b in batches)


# --------------------------------------------------------------------------
# checkpointing
# --------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    tree = {"a": jnp.arange(6).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
            "li": [jnp.zeros(2), jnp.ones(3)]}
    mgr.save(10, tree)
    out = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert out["nested"]["b"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["li"][1]),
                                  np.asarray(tree["li"][1]))


def test_checkpoint_keep_k_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    tree = {"x": jnp.zeros(1)}
    for s in (1, 2, 3):
        mgr.save(s, {"x": jnp.full((1,), float(s))})
    assert mgr.all_steps() == [2, 3]
    out = mgr.restore(tree)
    assert float(out["x"][0]) == 3.0


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=1, async_write=True)
    mgr.save(5, {"x": jnp.ones(4)})
    mgr.wait()
    assert mgr.latest_step() == 5


def test_checkpoint_shape_mismatch(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, {"x": jnp.ones(4)})
    with pytest.raises(ValueError, match="shape"):
        mgr.restore({"x": jnp.ones(5)})


def test_checkpoint_resume_bitexact(tmp_path):
    """restart-from-checkpoint reproduces the uninterrupted run."""
    from repro.configs import get_config
    from repro.train import OptConfig, init_train_state, make_train_step
    from repro.train.data import SyntheticDataset
    cfg = get_config("yi-9b", smoke=True)
    ocfg = OptConfig(lr=1e-3, warmup_steps=1, decay_steps=8)
    step_fn = make_train_step(cfg, ocfg, None, 2, kv_block=32, donate=False)
    ds = SyntheticDataset(cfg.vocab, 32, 2)

    state = init_train_state(jax.random.PRNGKey(0), cfg, ocfg, None)
    losses_full = []
    for i in range(4):
        state, m = step_fn(state, ds.batch_at(i))
        losses_full.append(float(m["loss"]))

    mgr = CheckpointManager(str(tmp_path), async_write=False)
    state2 = init_train_state(jax.random.PRNGKey(0), cfg, ocfg, None)
    for i in range(2):
        state2, _ = step_fn(state2, ds.batch_at(i))
    mgr.save(2, state2)
    state3 = mgr.restore(state2)
    losses_resumed = []
    for i in range(2, 4):
        state3, m = step_fn(state3, ds.batch_at(i))
        losses_resumed.append(float(m["loss"]))
    np.testing.assert_allclose(losses_resumed, losses_full[2:], rtol=1e-6)


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------

def test_chunked_ce_matches_direct(rng):
    b, s, d, v = 2, 16, 8, 50
    hidden = jnp.asarray(rng.randn(b, s, d).astype(np.float32))
    head = jnp.asarray(rng.randn(d, v).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, v, (b, s)))
    loss, metrics = chunked_cross_entropy(hidden, labels, head, n_chunks=4)
    logits = np.asarray(hidden @ head)
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    lab = np.take_along_axis(logits, np.asarray(labels)[..., None],
                             -1)[..., 0]
    ref = np.mean(lse - lab)
    np.testing.assert_allclose(float(loss), ref, rtol=1e-5)
    assert int(metrics["n_tokens"]) == b * s


def test_chunked_ce_ignores_padding(rng):
    b, s, d, v = 1, 8, 4, 10
    hidden = jnp.asarray(rng.randn(b, s, d).astype(np.float32))
    head = jnp.asarray(rng.randn(d, v).astype(np.float32))
    labels = jnp.asarray([[1, 2, 3, -1, -1, -1, -1, -1]])
    _, metrics = chunked_cross_entropy(hidden, labels, head, n_chunks=2)
    assert int(metrics["n_tokens"]) == 3


# --------------------------------------------------------------------------
# compression math
# --------------------------------------------------------------------------

def test_int8_quantize_bounds(rng):
    x = jnp.asarray(rng.randn(64).astype(np.float32) * 5)
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_error_feedback_conservation(rng):
    """q*scale + residual == input (+ carried residual) exactly."""
    x = jnp.asarray(rng.randn(32).astype(np.float32))
    res = jnp.asarray(rng.randn(32).astype(np.float32) * 0.01)
    q, scale, new_res = compress_residual(x, res)
    recon = np.asarray(dequantize_int8(q, scale)) + np.asarray(new_res)
    np.testing.assert_allclose(recon, np.asarray(x + res), atol=1e-6)


def test_topk_roundtrip(rng):
    x = jnp.asarray(rng.randn(100).astype(np.float32))
    vals, idx = topk_sparsify(x, 0.1)
    dense = np.asarray(topk_densify(vals, idx, (100,)))
    assert (dense != 0).sum() == 10
    top10 = np.argsort(-np.abs(np.asarray(x)))[:10]
    np.testing.assert_allclose(np.sort(dense[top10]),
                               np.sort(np.asarray(x)[top10]))
