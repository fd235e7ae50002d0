import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any jax import (jax locks the device
count at first init), which is why this module is the only place they live —
tests and benches keep seeing 1 CPU device.

For every cell this proves on 512 placeholder devices what would have to be
true on 512 real TPU v5e chips: the shardings are coherent, the collectives
lower, and the per-device memory fits.  The compiled artifact's
cost_analysis + parsed collective traffic feed EXPERIMENTS §Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs import (ARCH_IDS, SHAPES, cell_applicable, for_mode,
                           get_config, input_specs)
from repro.core import energy as energy_lib
from repro.launch import hlo_walker as walker_lib
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.train import make_prefill_step, make_serve_step, make_train_step
from repro.train.step import opt_state_shapes

HBM_PER_CHIP = 16 * 1024**3    # TPU v5e: 16 GB


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: Optional[Dict[str, Any]] = None,
               n_micro: int = 1):
    """Build the jitted step for one cell and return (lowered, meta)."""
    import dataclasses as _dc
    cell = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if cell.kind != "train":
        cfg = for_mode(cfg, "serve")
    if overrides:
        cfg = _dc.replace(cfg, **overrides)   # overrides take final precedence
    if not cell_applicable(cfg, cell):
        return None, {"skipped": True,
                      "reason": "long_500k needs sub-quadratic attention"}

    if cell.kind == "train":
        if n_micro == 0:
            # default: 2 sequences/device/microbatch; 1 for 314B grok —
            # capped so every microbatch keeps ≥1 row per data shard
            dp = mesh.devices.size // mesh.shape["model"]
            rows_per_dev = max(cell.global_batch // dp, 1)
            target = 16 if cfg.param_count() > 1e11 else 8
            n_micro = max(min(target, rows_per_dev), 1)
        batch = input_specs(cfg, cell)
        bundle = make_train_step(cfg, mesh, batch, n_micro=n_micro)
        params = api.param_shapes(cfg)
        opt = opt_state_shapes(cfg)
        args = (params, opt, batch)
        donate = (0, 1)          # params + optimizer state update in place
    elif cell.kind == "prefill":
        batch = input_specs(cfg, cell)
        bundle = make_prefill_step(cfg, mesh, batch)
        params = api.param_shapes(cfg)
        args = (params, batch)
        donate = ()
    else:  # decode
        spec = input_specs(cfg, cell)
        bundle = make_serve_step(cfg, mesh, cell.global_batch, cell.seq_len)
        params = api.param_shapes(cfg)
        args = (params, spec["cache"], spec["token"])
        donate = (1,)            # cache appended in place

    fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                 out_shardings=bundle.out_shardings, donate_argnums=donate)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
    meta = {"cfg": cfg, "mesh": mesh, "cell": cell, "bundle": bundle}
    return lowered, meta


def analyse(lowered, meta, compile_it: bool = True) -> Dict[str, Any]:
    cfg, mesh, cell = meta["cfg"], meta["mesh"], meta["cell"]
    chips = mesh.devices.size
    rec: Dict[str, Any] = {
        "arch": cfg.name, "shape": cell.name, "kind": cell.kind,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    t0 = time.time()
    if compile_it:
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)
        mem = compiled.memory_analysis()
        rec["arg_bytes_per_dev"] = int(mem.argument_size_in_bytes)
        rec["temp_bytes_per_dev"] = int(mem.temp_size_in_bytes)
        rec["out_bytes_per_dev"] = int(mem.output_size_in_bytes)
        rec["alias_bytes_per_dev"] = int(mem.alias_size_in_bytes)
        peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes +
                mem.output_size_in_bytes - mem.alias_size_in_bytes)
        rec["peak_bytes_per_dev"] = int(peak)
        rec["fits_hbm"] = bool(peak <= HBM_PER_CHIP)
        ca = compiled.cost_analysis()
        # raw XLA numbers (NOT trip-count-aware — kept for cross-checking)
        rec["xla_flops_per_dev"] = float(ca.get("flops", 0.0))
        rec["xla_bytes_per_dev"] = float(ca.get("bytes accessed", 0.0))
        txt = compiled.as_text()
    else:
        txt = lowered.as_text()
    # while-aware walker: multiplies scan bodies by trip count (XLA's own
    # cost_analysis counts a 62-layer scanned stack once — see hlo_walker)
    cost = walker_lib.module_cost(txt, chips)
    rec["hlo_flops_per_dev"] = cost.flops
    rec["hlo_bytes_per_dev"] = cost.bytes
    rec["collective_bytes_per_dev"] = cost.coll_bytes
    rec["collective_ops"] = cost.n_collectives
    rec["collective_by_kind"] = {k: round(v) for k, v in
                                 cost.coll_by_kind.items()}
    if cost.warnings:
        rec["walker_warnings"] = cost.warnings

    if compile_it:
        terms = energy_lib.roofline(
            rec["hlo_flops_per_dev"], rec["hlo_bytes_per_dev"],
            cost.coll_bytes, chips=1)     # walker numbers are per-device
        rec["t_compute_s"] = terms.t_compute
        rec["t_memory_s"] = terms.t_memory
        rec["t_collective_s"] = terms.t_collective
        rec["bottleneck"] = terms.bottleneck
        rec["roofline_fraction"] = terms.roofline_fraction
        # MODEL_FLOPS sanity ratio: useful model FLOPs vs compiled FLOPs
        rec["model_flops"] = model_flops(cfg, cell)
        total_hlo = rec["hlo_flops_per_dev"] * chips
        rec["model_vs_hlo"] = (rec["model_flops"] / total_hlo
                               if total_hlo else 0.0)
        rec["energy_wh_per_step"] = energy_lib.energy_wh(
            energy_lib.roofline(rec["hlo_flops_per_dev"] * chips,
                                rec["hlo_bytes_per_dev"] * chips,
                                cost.coll_bytes * chips, chips=chips))
    return rec


def model_flops(cfg, cell) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE) for train; 2·N·D for inference."""
    n = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * cell.global_batch   # decode: one token per sequence


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             compile_it: bool = True, n_micro: int = 0,
             overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    lowered, meta = lower_cell(arch, shape_name, multi_pod,
                               overrides=overrides, n_micro=n_micro)
    if lowered is None:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16", **meta}
    rec = analyse(lowered, meta, compile_it=compile_it)

    # XLA:CPU emulates bf16 by upcasting every op to f32, materializing f32
    # copies of weights/caches that do not exist on a real TPU (bf16-native
    # MXU/VPU).  When the raw CPU-measured peak misses the HBM budget, we
    # recompile the cell with f32 end-to-end (no convert artifacts) and
    # estimate the TPU peak as exact bf16 args/outs + temp_f32 / 2.
    if compile_it and not rec.get("fits_hbm", True):
        ov = dict(overrides or {})
        ov.update(dtype="float32", param_dtype="float32")
        try:
            l32, m32 = lower_cell(arch, shape_name, multi_pod,
                                  overrides=ov, n_micro=n_micro)
            mem32 = l32.compile().memory_analysis()
            corrected = (rec["arg_bytes_per_dev"] + rec["out_bytes_per_dev"]
                         - rec["alias_bytes_per_dev"]
                         + mem32.temp_size_in_bytes // 2)
            rec["temp_f32_bytes_per_dev"] = int(mem32.temp_size_in_bytes)
            rec["peak_bytes_tpu_est"] = int(corrected)
            rec["fits_hbm_tpu_est"] = bool(corrected <= HBM_PER_CHIP)
        except Exception as e:  # noqa: BLE001
            rec["tpu_correction_error"] = f"{type(e).__name__}: {e}"
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-compile", action="store_true",
                    help="lower only (debug)")
    ap.add_argument("--n-micro", type=int, default=0,
                    help="microbatches for train cells (0 = auto: 8)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch/--shape or --all required")
        cells = [(args.arch, args.shape)]

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    failures = 0
    for arch, shape in cells:
        tag = f"{arch}__{shape}__{mesh_tag}"
        try:
            rec = run_cell(arch, shape, args.multi_pod,
                           compile_it=not args.no_compile,
                           n_micro=args.n_micro)
            status = ("SKIP" if rec.get("skipped")
                      else "OK" if rec.get("fits_hbm", True)
                      else "OK*" if rec.get("fits_hbm_tpu_est") else "OOM")
            print(f"[{status}] {tag}: "
                  f"peak={rec.get('peak_bytes_per_dev', 0)/2**30:.2f}GiB "
                  f"flops/dev={rec.get('hlo_flops_per_dev', 0):.3g} "
                  f"coll={rec.get('collective_bytes_per_dev', 0)/1e6:.1f}MB "
                  f"bottleneck={rec.get('bottleneck', '-')}")
            if status == "OOM":
                failures += 1
        except Exception as e:  # noqa: BLE001 — report and continue the sweep
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                   "error": f"{type(e).__name__}: {e}"}
            print(f"[FAIL] {tag}: {e}")
            failures += 1
        (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    print(f"done: {len(cells)} cells, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
