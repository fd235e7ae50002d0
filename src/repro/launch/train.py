"""Training launcher: data → sharded train loop → checkpoints → recovery.

Runs end-to-end on this host with a reduced config (examples/train_100m.py)
and lowers unchanged on the production mesh (launch/dryrun.py exercises the
identical step bundle at 512 chips).  Fault tolerance: periodic atomic
checkpoints, a step-time watchdog, restart-from-LATEST (optionally on an
elastically degraded mesh via --lost-chips).

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.distributed import checkpoint as ckpt
from repro.distributed.elastic import build_mesh, plan_remesh
from repro.distributed.fault import StragglerDetector, TrainWatchdog
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import api
from repro.train import OptConfig, make_opt_state, make_train_step


def synthetic_batches(cfg, batch: int, seq: int, seed: int = 0
                      ) -> Iterator[dict]:
    """Deterministic LM data: next-token prediction over structured noise."""
    rng = np.random.default_rng(seed)
    while True:
        # Markov-ish stream so there is real signal to learn
        start = rng.integers(0, cfg.vocab_size - 1, size=(batch, 1))
        steps = rng.integers(1, 7, size=(batch, seq))
        toks = (start + np.cumsum(steps, axis=1)) % cfg.vocab_size
        b = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
        if cfg.layout == "encdec" or cfg.frontend == "audio":
            b["frames"] = jnp.asarray(
                rng.standard_normal((batch, cfg.n_frontend_tokens,
                                     cfg.d_model)), cfg.dtype)
        elif cfg.frontend == "vision":
            b["frontend_embeddings"] = jnp.asarray(
                rng.standard_normal((batch, cfg.n_frontend_tokens,
                                     cfg.d_model)), cfg.dtype)
        yield b


def train(arch: str, smoke: bool, steps: int, batch: int, seq: int,
          ckpt_dir: Optional[str], ckpt_every: int = 20,
          n_micro: int = 1, use_ef_compress: bool = False,
          production_mesh: bool = False, lost_chips: int = 0,
          fail_at_step: Optional[int] = None, lr: float = 3e-4,
          log_every: int = 10) -> dict:
    cfg = get_config(arch, smoke=smoke)
    mesh = (make_production_mesh() if production_mesh else make_host_mesh())
    if lost_chips:
        mesh = build_mesh(plan_remesh(mesh, lost_chips))
    opt_cfg = OptConfig(lr=lr, total_steps=max(steps, 2), warmup_steps=min(
        20, steps // 5 + 1), moment_dtype=cfg.moment_dtype)

    data = synthetic_batches(cfg, batch, seq + 1)
    example = next(data)
    bundle = make_train_step(cfg, mesh, example, opt_cfg, n_micro=n_micro,
                             use_ef_compress=use_ef_compress,
                             loss_chunk=min(512, seq))
    step_fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                      out_shardings=bundle.out_shardings,
                      donate_argnums=(0, 1))

    params = api.init_params(cfg, jax.random.PRNGKey(0))
    opt = make_opt_state(cfg, params, opt_cfg, use_ef_compress)
    start_step = 0
    if ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
        (params, opt), extra = ckpt.restore(
            ckpt_dir, (params, opt))
        start_step = int(extra.get("step", 0))
        print(f"[train] restored step {start_step} from {ckpt_dir}")

    watchdog = TrainWatchdog(ckpt_dir or "/tmp/ckpt")
    straggle = StragglerDetector()
    losses = []
    step = start_step
    with jax.set_mesh(mesh):
        while step < steps:
            t0 = time.monotonic()
            batch_data = next(data)
            try:
                if fail_at_step is not None and step == fail_at_step:
                    fail_at_step = None
                    raise RuntimeError("injected failure")
                params, opt, metrics = step_fn(params, opt, batch_data)
            except RuntimeError as e:
                if not ckpt_dir or not watchdog.should_restart():
                    raise
                print(f"[train] step {step} failed ({e}); restoring")
                restore_step = watchdog.on_failure()
                params = api.init_params(cfg, jax.random.PRNGKey(0))
                opt = make_opt_state(cfg, params, opt_cfg, use_ef_compress)
                (params, opt), extra = ckpt.restore(ckpt_dir, (params, opt))
                step = int(extra.get("step", restore_step))
                continue
            straggle.record("host0", time.monotonic() - t0)
            loss = float(metrics["loss"])
            losses.append(loss)
            step += 1
            if step % log_every == 0 or step == steps:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f}")
            if ckpt_dir and step % ckpt_every == 0:
                ckpt.save(ckpt_dir, step, (params, opt),
                          extra={"step": step, "arch": arch})
                ckpt.prune(ckpt_dir, keep=3)
    if ckpt_dir:
        ckpt.save(ckpt_dir, step, (params, opt),
                  extra={"step": step, "arch": arch})
    return {"final_loss": losses[-1] if losses else float("nan"),
            "first_loss": losses[0] if losses else float("nan"),
            "steps": step, "losses": losses}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="rwkv6-1.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--ef-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a failure (tests recovery)")
    args = ap.parse_args()
    out = train(args.arch, args.smoke, args.steps, args.batch, args.seq,
                args.ckpt_dir, args.ckpt_every, args.n_micro,
                args.ef_compress, fail_at_step=args.fail_at_step, lr=args.lr)
    print(f"[train] done: loss {out['first_loss']:.3f} -> "
          f"{out['final_loss']:.3f} over {out['steps']} steps")


if __name__ == "__main__":
    main()
