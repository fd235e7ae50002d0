"""Per-arch reduced-config smoke: one forward/train step on CPU, asserting
output shapes and finiteness (the FULL configs are exercised only via the
dry-run's ShapeDtypeStructs — deliverable (f))."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import api


def _batch(cfg, b=2, s=32, key=0):
    k = jax.random.PRNGKey(key)
    toks = jax.random.randint(k, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.layout == "encdec" or cfg.frontend == "audio":
        batch["frames"] = jax.random.normal(
            k, (b, cfg.n_frontend_tokens, cfg.d_model)).astype(cfg.dtype)
    elif cfg.frontend == "vision":
        batch["frontend_embeddings"] = jax.random.normal(
            k, (b, cfg.n_frontend_tokens, cfg.d_model)).astype(cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_loss(arch):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, metrics = api.loss_fn(params, batch, cfg, loss_chunk=16)
    assert jnp.isfinite(loss), arch
    assert float(loss) > 0
    assert jnp.isfinite(metrics["aux"])


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step_reduces_loss(arch):
    from repro.launch.mesh import make_host_mesh
    from repro.train import OptConfig, make_opt_state, make_train_step
    cfg = get_config(arch, smoke=True)
    mesh = make_host_mesh()
    batch = _batch(cfg, b=2, s=32)
    bundle = make_train_step(cfg, mesh, batch,
                             OptConfig(lr=5e-3, total_steps=8,
                                       warmup_steps=0,
                                       moment_dtype=cfg.moment_dtype),
                             loss_chunk=16)
    fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                 out_shardings=bundle.out_shardings)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    opt = make_opt_state(cfg, params)
    with jax.set_mesh(mesh):
        losses = []
        for _ in range(4):
            params, opt, m = fn(params, opt, batch)
            losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), arch
    assert min(losses[1:]) < losses[0], (arch, losses)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serve_step_shapes(arch):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    b, max_len = 2, 64
    cache = api.init_cache(cfg, b, max_len)
    tok = jnp.ones((b, 1), jnp.int32)
    logits, cache = api.serve_step(params, tok, cache, cfg)
    assert logits.shape == (b, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32)))), arch
    np.testing.assert_array_equal(np.asarray(cache["length"]), [1, 1])


@pytest.mark.parametrize("arch", ["granite-3-8b", "rwkv6-1.6b", "zamba2-7b"])
def test_decode_matches_teacher_forcing(arch):
    """Step-by-step decode logits == teacher-forced forward logits."""
    cfg = get_config(arch, smoke=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    b, s = 1, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    logits_tf, _ = api.forward(params, {"tokens": toks}, cfg)
    cache = api.init_cache(cfg, b, 32)
    errs = []
    for t in range(s):
        lg, cache = api.serve_step(params, toks[:, t:t + 1], cache, cfg)
        errs.append(float(jnp.max(jnp.abs(
            lg[:, 0].astype(jnp.float32)
            - logits_tf[:, t].astype(jnp.float32)))))
    # bf16 accumulation-order noise; mamba's chunked-vs-stepwise f32 state
    # recurrence drifts most (still ≪ logit scale ~20)
    tol = 0.6 if cfg.layout == "mamba_hybrid" else 0.15
    assert max(errs) < tol, (arch, errs)


def test_windowed_cache_smaller_than_uniform():
    cfg = get_config("gemma3-27b")
    from repro.models import lm
    windowed = lm.cache_shapes(cfg, 128, 32768)
    assert "k_local" in windowed
    uniform_bytes = (cfg.n_layers * 128 * 32768 * cfg.n_kv_heads
                     * cfg.head_dim * 2 * 2)
    windowed_bytes = sum(
        np.prod(v.shape) * 2 for k, v in windowed.items() if k != "length")
    assert windowed_bytes < 0.25 * uniform_bytes


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_shapes_match_init(arch):
    cfg = get_config(arch, smoke=True)
    shapes = api.param_shapes(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    flat_s = jax.tree.leaves(shapes)
    flat_p = jax.tree.leaves(params)
    assert len(flat_s) == len(flat_p)
    for s, p in zip(flat_s, flat_p):
        assert tuple(s.shape) == tuple(p.shape)
        assert s.dtype == p.dtype
