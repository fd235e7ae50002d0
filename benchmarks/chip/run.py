#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload pool2.paper-mix.steady \\
        --seed 12345 --seconds 40 --trace 0

Reads the cell from ``BENCHMARK.json`` at the checkout's root, builds it
on the chips JAX finds, serves its open loop for ``--seconds`` after the
warm-in, and prints one JSON object as the last line of standard output:
the end-to-end metrics with ``--trace 0``, the per-layer metrics (from a
profiler trace of a slice of the window) with ``--trace 1``.  The numbers
that decide ``correct`` are printed beside their limits as the last lines
of standard error.  Exits with status 2, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.

JAX's persistent compilation cache is kept in ``<checkout>/.jax_cache``,
so that only a checkout's first run of a cell compiles.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # before JAX is imported: JAX reads the variable when it loads
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import harness
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload, ROOT)
    try:
        devices = harness.tpu_devices(cell.chips)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices, T_PROCESS)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
