"""Training substrate: optimizer math, grad compression, microbatching."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.data import TokenLoader
from repro.models.transformer import LM
from repro.training import (AdamWConfig, adamw_init, adamw_update,
                            clip_by_global_norm, make_train_step,
                            quantize_int8, dequantize_int8)
from repro.training.grad_compression import compressed_grad_sync, init_residuals


def test_adamw_first_step_is_lr_sized():
    cfg = AdamWConfig(lr=1e-2, warmup_steps=1, weight_decay=0.0)
    params = {"w": jnp.ones((4, 4))}
    grads = {"w": jnp.full((4, 4), 0.5)}
    state = adamw_init(params)
    new, state = adamw_update(cfg, params, grads, state)
    # bias-corrected first Adam step == lr * sign-ish step
    delta = np.asarray(params["w"] - new["w"])
    np.testing.assert_allclose(delta, 1e-2, rtol=1e-3)
    assert int(state["step"]) == 1


def test_grad_clip():
    tree = {"a": jnp.full((10,), 10.0)}
    clipped, gn = clip_by_global_norm(tree, 1.0)
    assert float(gn) > 1.0
    from repro.training.optimizer import global_norm
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (256,)).astype(np.float32))
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) * 0.51 + 1e-6


def test_compressed_sync_single_shard_with_error_feedback():
    """On a 1-device axis the compressed mean must equal plain quantization,
    and error feedback must cancel bias over repeated steps."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(0, 1, (64,))
                          .astype(np.float32))}
    res = init_residuals(g)

    def run(gw, rw):
        out, nr = compressed_grad_sync({"w": gw}, "data", {"w": rw})
        return out["w"], nr["w"]

    f = jax.shard_map(run, mesh=mesh, in_specs=(P(), P()),
                      out_specs=(P(), P()), check_vma=False)
    acc = jnp.zeros_like(g["w"])
    r = res["w"]
    for _ in range(16):
        o, r = f(g["w"], r)
        acc = acc + o
    # mean of 16 compressed syncs of the same grad ~ the grad (EF kills bias)
    np.testing.assert_allclose(np.asarray(acc / 16), np.asarray(g["w"]),
                               atol=0.02)


def test_microbatch_equals_full_batch():
    cfg = configs.get_smoke_config("olmo-1b").scaled(n_layers=2, vocab=64)
    lm = LM(cfg)
    params = lm.init(jax.random.key(0))
    loader = TokenLoader(vocab=cfg.vocab, batch=8, seq_len=32, seed=2)
    batch = loader.batch_at(0)
    s1 = make_train_step(lm, opt_cfg=AdamWConfig(lr=1e-3), microbatches=1)
    s2 = make_train_step(lm, opt_cfg=AdamWConfig(lr=1e-3), microbatches=4)
    from repro.training import adamw_init
    p1, _, m1 = s1(params, adamw_init(params), batch)
    p2, _, m2 = s2(params, adamw_init(params), batch)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
