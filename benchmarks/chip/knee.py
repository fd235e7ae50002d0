#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate its deployment sustains.

    python3 benchmarks/chip/knee.py --workload pool2.paper-mix.steady \\
        --seed 7 --rates 1.5 2 2.5 3 --seconds 30 --out knee.jsonl

One process builds the cell once and serves its mix at each rate in turn
(ascending; the pool is drained between rates), each for a warm-in and a
window.  A rate is sustained when the backlog (arrivals parked for a slot
plus requests queued at an engine) does not grow over the window and
every request due in the window is answered within the drain.  Prints one
JSON line per rate, and the knee last.  Run it once, on a chip, when a
cell's rates are set; the rates then go into the load files as numbers.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
UID_STRIDE = 1_000_000    # uids of the k-th rate start at k x this


def backlog(dep) -> int:
    srv = dep.server
    return len(srv.arrivals) + sum(len(e.queue) for e in srv.engines.values())


def slope(samples) -> float:
    """Least-squares growth of the backlog, requests per second."""
    n = len(samples)
    if n < 2:
        return 0.0
    mt = sum(t for t, _ in samples) / n
    mb = sum(b for _, b in samples) / n
    den = sum((t - mt) ** 2 for t, _ in samples)
    return sum((t - mt) * (b - mb) for t, b in samples) / den if den else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True,
                    help="offered rates, requests/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--warm", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import harness
    import latency
    import mix
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload, ROOT)
    devices = harness.tpu_devices(cell.chips)
    enable_compile_cache()
    streams = []
    for k, rate in enumerate(sorted(args.rates)):
        load = dict(cell.load, rate_qps=rate, warm_s=args.warm)
        stream = [dataclasses.replace(a, uid=a.uid + UID_STRIDE * k)
                  for a in mix.generate(load, args.seconds)]
        streams.append((rate, load, stream))
    _, dep, every = harness.build(cell, args.seed,
                                  [a for _, _, s in streams for a in s],
                                  devices[0])
    reqs_by_rate = [[r for r in every if r.uid // UID_STRIDE == k]
                    for k in range(len(streams))]
    calls = []
    harness.instrument(dep, calls, harness.RouteCapture())
    print(f"[knee] set-up {time.monotonic() - T_PROCESS:.1f} s",
          file=sys.stderr, flush=True)

    rows, knee = [], None
    for (rate, load, _), reqs in zip(streams, reqs_by_rate):
        samples = []
        orig_step = dep.server.step

        def step():
            orig_step()
            samples.append((time.monotonic(), backlog(dep)))
        dep.server.step = step
        loop = harness.drive(dep, reqs, time.monotonic(), load["warm_s"],
                             args.seconds, None, harness.RouteCapture())
        dep.server.step = orig_step
        t0, t1 = loop["window"]
        end = harness.drain(dep, reqs, loop["window"], loop["stamp"],
                            load["drain_limit_s"])
        win = [r for r in reqs if t0 <= r.due < t1]
        answered = [r for r in win if r.answered]
        inside = [(t, b) for t, b in samples if t0 <= t <= t1]
        row = {"rate": rate, "due": len(win),
               "answered": len(answered),
               "backlog_slope_per_s": slope(inside),
               "backlog_max": max((b for _, b in inside), default=0),
               "backlog_end": inside[-1][1] if inside else 0,
               "ttft_p90_ms": 1e3 * latency.ttft_tail(
                   [(r.due, r.stamps[0] if r.stamps else None, end)
                    for r in win], 0.90) if win else None,
               "output_tokens_per_s": sum(latency.count_in(r.stamps, t0, t1)
                                          for r in reqs) / (t1 - t0),
               "routed": {m: sum(r.model == m for r in win)
                          for m in cell.models},
               "drain_s": end - t1}
        sustained = (len(answered) == len(win)
                     and row["backlog_slope_per_s"] < 0.05 * rate)
        row["sustained"] = sustained
        if sustained:
            knee = rate
        rows.append(row)
        print(json.dumps(row), flush=True)
        while dep.busy():      # settle before the next rate
            dep.server.step()
    print(json.dumps({"knee": knee}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write("".join(json.dumps(r) + "\n" for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
