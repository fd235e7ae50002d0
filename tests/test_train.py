"""Optimizer, schedules, gradient compression, end-to-end training loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import (OptConfig, adamw_update, cosine_lr, ef_compress,
                         ef_init, init_opt_state)
from repro.train.compress import dequantize_leaf, quantize_leaf


def test_cosine_schedule_shape():
    cfg = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100,
                    min_lr_frac=0.1)
    lrs = [float(cosine_lr(cfg, jnp.int32(s))) for s in range(0, 101, 10)]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(1e-3, rel=1e-4)     # end of warmup
    assert lrs[-1] == pytest.approx(1e-4, rel=1e-2)    # min_lr_frac·lr
    assert all(a >= b - 1e-9 for a, b in zip(lrs[1:], lrs[2:]))


def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    cfg = OptConfig(lr=0.2, weight_decay=0.0, total_steps=200,
                    warmup_steps=0, min_lr_frac=1.0)
    opt = init_opt_state(params, cfg)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(grads, opt, params, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_moment_dtype_respected():
    params = {"w": jnp.ones((4, 4))}
    cfg = OptConfig(moment_dtype="bfloat16")
    opt = init_opt_state(params, cfg)
    assert opt["m"]["w"].dtype == jnp.bfloat16
    params2, opt2, _ = adamw_update({"w": jnp.ones((4, 4))}, opt, params, cfg)
    assert opt2["v"]["w"].dtype == jnp.bfloat16


def test_grad_clip_applied():
    params = {"w": jnp.zeros(3)}
    cfg = OptConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0, warmup_steps=0,
                    total_steps=10, min_lr_frac=1.0)
    opt = init_opt_state(params, cfg)
    _, _, m = adamw_update({"w": jnp.array([30.0, 40.0, 0.0])}, opt, params,
                           cfg)
    assert float(m["grad_norm"]) == pytest.approx(50.0, rel=1e-5)


def test_ef_compress_residual_carries():
    """Error feedback: compressed-sum over steps ≈ true sum."""
    rng = np.random.default_rng(0)
    grads = [{"w": jnp.asarray(rng.standard_normal(64), dtype=jnp.float32)}
             for _ in range(30)]
    ef = ef_init({"w": jnp.zeros(64)})
    total_c = np.zeros(64)
    total_t = np.zeros(64)
    for g in grads:
        gq, ef = ef_compress(g, ef)
        total_c += np.asarray(gq["w"])
        total_t += np.asarray(g["w"])
    # residual bound: the final EF buffer is the only divergence
    np.testing.assert_allclose(total_c + np.asarray(ef["w"]), total_t,
                               atol=1e-4)
    assert np.abs(total_c - total_t).max() < 0.05


def test_quantize_roundtrip_exact_for_grid_values():
    x = jnp.asarray(np.linspace(-127, 127, 255), dtype=jnp.float32)
    q, s = quantize_leaf(x)
    np.testing.assert_allclose(np.asarray(dequantize_leaf(q, s)),
                               np.asarray(x), atol=1e-4)


def test_train_loop_with_failure_recovery(tmp_path):
    """launch.train end-to-end: loss drops; injected failure restores from
    checkpoint and continues to the target step."""
    from repro.launch.train import train
    out = train("rwkv6-1.6b", smoke=True, steps=8, batch=2, seq=32,
                ckpt_dir=str(tmp_path), ckpt_every=3, fail_at_step=5,
                lr=5e-3, log_every=100)
    assert out["steps"] == 8                     # recovered AND finished
    assert np.isfinite(out["final_loss"])
    assert len(out["losses"]) >= 8               # re-ran the restored span


def test_compressed_psum_subprocess():
    """int8 compressed all-reduce ≈ fp32 sum across 8 fake devices."""
    import subprocess, sys, os, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.train.compress import compressed_psum
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((8, 32, 16)), jnp.float32)
        with jax.set_mesh(mesh):
            out = compressed_psum({"w": g}, "data", mesh)
        want = np.asarray(g).sum(0)
        got = np.asarray(out["w"])
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 0.02, rel
        print("OK", rel)
    """)
    r = subprocess.run([sys.executable, "-c", code],
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=300)
    assert "OK" in r.stdout, r.stderr[-2000:]
