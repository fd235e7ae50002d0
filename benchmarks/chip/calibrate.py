#!/usr/bin/env python3
"""Readings that the correctness limits are set from.

    python3 benchmarks/chip/calibrate.py --workload pool2.paper-mix.steady \\
        --seeds 101 102 103 --control 3 --seconds 10 --warm 5 --out cal.jsonl

Runs the cell once per seed in one process (weights drawn from each seed,
the cell's one stream for all; programs compiled once), each at the cell's
own load for a short window whose requests are all drained, and compares the same
sample as a benchmark run does.  Prints, per seed, every model's widest
gap of the served tokens (the sound reading) and, for the first
``--control`` seeds, for each control format, the same numbers of the
tokens the control puts first at the same positions (its reading), with
the router's numbers in ``high`` precision beside them, and whether the
control passes the cell's limits (it must not).
``PERF.md`` records the readings and the limits set between them.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="read the controls on the first N seeds")
    ap.add_argument("--formats", nargs="+", default=["int8", "fp8"],
                    choices=["int8", "fp8"],
                    help="the control formats")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--warm", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import harness
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload, ROOT)
    devices = harness.tpu_devices(cell.chips)
    enable_compile_cache()
    cell.load = dict(cell.load, warm_s=args.warm)
    rows = []
    for i, seed in enumerate(args.seeds):
        res = harness.run(cell, seed, args.seconds, False, devices,
                          time.monotonic(),
                          controls=tuple(args.formats) if i < args.control
                          else ())
        row = {"seed": seed, "correct": res["correct"],
               "attempted": res["attempted"], "failed": res["failed"],
               "numbers": {k: v["value"] for k, v in res["checks"].items()},
               "controls": {f: {"correct": c["correct"],
                                "numbers": {k: v["value"] for k, v in
                                            c["checks"].items()}}
                            for f, c in res.get("controls", {}).items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write("".join(json.dumps(r) + "\n" for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
