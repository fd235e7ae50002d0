"""Run one benchmark cell once: build, warm up, serve an open loop, measure.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything about
it is found by name: its configuration file (``configs``), its load file
and the mix that names (``traffic/``), and a reader file for each of its
per-layer metrics (``layers/<metric>.py``), and for each model of the
configuration its layout's weights, plain reference and operation counts
(``arch/<layout>.py``, see ``layouts.py``).  A new cell needs new entries
and data files, and a new architecture one ``arch/<layout>.py`` beside its
config file and cell; neither edits a file here.

The deployment is built from the program's own parts: ``ModelEngine`` per
model (weights from ``weights.py``), ``GreenServRouter``, ``GreenCache``
with prefix reuse, and ``PoolServer``.  The open loop then sends the
cell's stream at its due times for the warm-in and the window, stepping
the server whenever it has work, and stamps every served token when the
step that produced it returns.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import monitoring

from repro.cache import GreenCache
from repro.configs import for_mode
from repro.core.pool import ModelPool
from repro.core.router import GreenServRouter
from repro.core.types import Query, RouterConfig, TaskType
from repro.data import tokenizer as tok
from repro.models import api
from repro.models.config import ModelConfig
from repro.serving import ModelEngine, PoolServer

import check
import flops
import latency
import mix
import peaks as peaks_lib
import route_ref
import trace_reduce
import weights as weights_lib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
TRAFFIC = BENCH_DIR.relative_to(ROOT) / "traffic"
SPAN = trace_reduce.SPAN


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- compile counting ----------------------------------------------------------

COMPILES = {"traces": 0, "backend_compiles": 0}


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == "/jax/core/compile/jaxpr_trace_duration":
        COMPILES["traces"] += 1
    elif event == "/jax/core/compile/backend_compile_duration":
        COMPILES["backend_compiles"] += 1


monitoring.register_event_duration_secs_listener(_on_duration)


# -- the cell ------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    load: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def models(self) -> Dict[str, dict]:
        return {m["name"]: m["config"] for m in self.config["models"]}


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r}; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=json.loads((root / cfg_entry["file"]).read_text()),
        load=mix.load_traffic(entry["traffic"], root / TRAFFIC),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def layer_reader(name: str, directory: pathlib.Path = BENCH_DIR / "layers"
                 ) -> Callable:
    """The ``read`` function of ``layers/<name>.py``."""
    path = directory / f"{name}.py"
    mod_name = "layer_" + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; raises ``NoChip`` otherwise."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


# -- records -------------------------------------------------------------------


@dataclasses.dataclass
class Req:
    """One request of the stream as the open loop saw it."""

    arrival: mix.Arrival
    query: Query
    prompt: List[int]
    due: float = 0.0                 # monotonic seconds
    sent: Optional[float] = None     # when the loop enqueued it
    request: object = None           # the program's Request once admitted
    stamps: List[float] = dataclasses.field(default_factory=list)

    @property
    def uid(self) -> int:
        return self.arrival.uid

    @property
    def model(self) -> str:
        return self.request.model_name if self.request else ""

    @property
    def generated(self) -> List[int]:
        return list(self.request.generated) if self.request else []

    @property
    def prefix_reused(self) -> int:
        return self.request.prefix_reused if self.request else 0

    @property
    def done(self) -> bool:
        return self.request is not None and self.request.done

    @property
    def answered(self) -> bool:
        return self.request is not None and self.request.state.value == "done"


@dataclasses.dataclass
class Call:
    """One engine tick: ``fed`` lists (first position, tokens) computed per
    live slot, spliced prefixes left out."""

    model: str
    kind: str            # "chunk" (greedy_chunk_step) | "decode" (greedy_step)
    t0: float
    t1: float
    fed: List[Tuple[int, int]]


@dataclasses.dataclass
class Run:
    """What a per-layer reader reads (``layers/<metric>.py``)."""

    cell: Cell
    peaks: peaks_lib.Peaks
    chips: int
    window: Tuple[float, float]
    requests: List[Req]
    calls: List[Call]
    counters: Dict[str, Dict[str, float]]   # "start"/"end" snapshots
    prefix_models: frozenset                # models with a prefix cache
    planes: frozenset                       # the devices' trace planes
    trace: Optional[dict] = None            # see ``_reduce_trace``
    stall: Tuple[float, float] = (0.0, 0.0)  # the loop stopping the profiler
    flops = flops
    latency = latency

    def in_window(self) -> List[Req]:
        lo, hi = self.window
        return [r for r in self.requests
                if lo <= r.due < hi and r.sent is not None]


# -- the deployment --------------------------------------------------------------


class Deployment:
    """The cell's pool: one ``PoolServer`` over one engine per model."""

    def __init__(self, cell: Cell, weights: Dict[str, dict], device,
                 accuracy: Callable):
        cfg = cell.config
        serving, rcfg = cfg["serving"], cfg["router"]
        with jax.default_device(device):
            engines = {
                m["name"]: ModelEngine(
                    m["name"], ModelConfig(name=m["name"], **m["config"]),
                    jax.random.PRNGKey(0), max_batch=serving["slots"],
                    max_len=serving["max_len"], params=weights[m["name"]],
                    detokenize=tok.decode,
                    prefill_chunk=serving["prefill_chunk"], device=device)
                for m in cfg["models"]}
            router = GreenServRouter(
                RouterConfig(**rcfg),
                ModelPool([e.profile for e in engines.values()]))
            self.server = PoolServer(
                router, engines, tokenizer=tok.encode,
                prefill_chunk=serving["prefill_chunk"],
                cache=GreenCache(mode="prefix",
                                 kv_cache_blocks=serving["kv_cache_blocks"]),
                accuracy_fn=accuracy)

    def engines(self):
        return self.server.engines.items()

    def busy(self) -> bool:
        return bool(self.server.inflight or self.server.arrivals)

    def counters(self) -> Dict[str, float]:
        router = self.server.router
        return {"decision_ms": router.decision_ms_total,
                "n_routed": float(router.n_routed),
                **{k: float(v) for k, v in COMPILES.items()}}

    def free(self) -> None:
        """Drop the program's device state (caches); weights stay."""
        for _, eng in self.engines():
            eng.cache = None
        self.server = None


def plane_name(device) -> str:
    """The trace plane the profiler gives ``device``."""
    return f"/device:{device.platform.upper()}:{device.id}"


def check_layout(params: dict, mcfg: ModelConfig) -> None:
    """The benchmark's weight tree has the program's layout and types."""
    want = api.param_shapes(for_mode(mcfg, "serve"))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (w.shape, w.dtype) != (g.shape, g.dtype)
            for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{mcfg.name}: benchmark weights do not match the "
                         f"program's parameter layout")


# -- warm-up -------------------------------------------------------------------


def warm_up(dep: Deployment, prompts: List[List[int]], texts: List[str],
            rcfg: RouterConfig) -> None:
    """Compile every program the cell's traffic reaches, before the loop.

    Both tick programs of every engine; the routing program at every
    padded batch size and feature width an admission can reach (on a
    throw-away router, so the served router's state is untouched); and
    the eager programs the engines run per shape: each prompt length's
    prefix capture, each splice length, each slot's length read."""
    lengths = sorted({len(p) for p in prompts})
    for name, eng in dep.engines():
        for i in range(eng.max_batch):
            int(eng.cache["length"][i])
        if eng.prefix_cache is not None:
            block = eng.prefix_cache.block_tokens
            k = eng.cache["k"]
            for n in lengths:
                if block <= n <= eng.max_len - 1:
                    np.asarray(k[:, 0, :n])
            splices = set(range(block, lengths[-1], block))
            splices |= {n - 1 for n in lengths}
            for p in sorted(s for s in splices if 0 < s < eng.max_len):
                blk = np.zeros((k.shape[0], p) + k.shape[3:], k.dtype)
                eng.cache = api.splice_prefix(eng.cache, 0, blk, blk)
                k = eng.cache["k"]
        eng.warmup()
    pool = dep.server.router.pool
    throwaway = GreenServRouter(dataclasses.replace(rcfg),
                                ModelPool([pool[i] for i in range(len(pool))]))
    _warm_routing(throwaway, texts,
                  sum(e.max_batch for _, e in dep.engines()))


def _warm_routing(router: GreenServRouter, texts: List[str],
                  max_batch: int) -> None:
    ctx = router.context
    width = {}
    for t in sorted(set(texts)):
        ids, _ = ctx.padded_feature_tensors([t], True, True, 1)
        width.setdefault(ids.shape[1], []).append(t)
    widths = sorted(width)
    q = 1
    while True:
        for w in widths:
            narrow = [t for v in widths if v <= w for t in width[v]]
            batch = [width[w][0]] + [narrow[i % len(narrow)]
                                     for i in range(q - 1)]
            router.route_batch([Query(uid=10 ** 9 + i, text=t)
                                for i, t in enumerate(batch)])
        if q >= max_batch:
            break
        q *= 2


# -- instrumentation -------------------------------------------------------------


def _span(name: str, fn: Callable) -> Callable:
    label = SPAN + name

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(label):
            return fn(*args, **kwargs)
    return wrapped


def _fed_positions(req) -> int:
    """Positions in the request's cache: prompt fed (spliced included) and
    every served token but the newest, which the next tick feeds."""
    return req.n_prompt_fed + max(len(req.generated) - 1, 0)


@dataclasses.dataclass
class RouteCapture:
    """Each routing decision made while ``on``: the queries' texts, a copy
    of the router state the decision read, and the program's arms and
    masked scores (before the scheduler's energy tilts)."""

    on: bool = False
    calls: List[dict] = dataclasses.field(default_factory=list)


def _router_state(router, resident: bool) -> dict:
    """Device-side copies (no host round trip) of the bandit's ``A_inv``,
    ``theta`` and ``active`` and of the k-means state."""
    st, km = router.policy.state, router.context.kmeans
    if resident:
        cent, cnt, ini = km.device_state()
    else:
        d = km.state_dict()
        cent, cnt, ini = d["centroids"], d["counts"], d["initialized"]
    state = dict(A_inv=st.A_inv, theta=st.theta, active=st.active,
                 centroids=cent,
                 counts=cnt, initialized=ini)
    return {k: jnp.copy(v) if isinstance(v, jax.Array) else np.array(v)
            for k, v in state.items()}


def _capturing(router, name: str, routes: RouteCapture) -> None:
    fn = getattr(router, name)
    resident = name == "_featurize_score_device"

    def wrapped(queries, *args, **kwargs):
        if not routes.on:
            return fn(queries, *args, **kwargs)
        state = _router_state(router, resident)
        out = fn(queries, *args, **kwargs)
        routes.calls.append({"texts": [q.text for q in queries],
                             "state": state, "arms": out[1],
                             "scores": out[2]})
        return out
    setattr(router, name, wrapped)


def instrument(dep: Deployment, calls: List[Call],
               routes: RouteCapture) -> None:
    """Spans around the scheduler's step, each engine tick, routing and
    feedback; a ``Call`` record of every engine tick; and the routing
    decisions into ``routes``."""
    computed: Dict[int, int] = {}

    def engine_step(eng: ModelEngine) -> Callable:
        step = eng.step
        label = f"{SPAN}engine.step:{eng.name}"

        def wrapped():
            cands = [r for r in eng.slots if r is not None] + list(eng.queue)
            n0, c0 = eng.n_steps, eng.n_chunk_steps
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(label):
                out = step()
            t1 = time.monotonic()
            if eng.n_steps > n0:
                fed = []
                for r in cands:
                    pos = _fed_positions(r)
                    done = pos - r.prefix_reused
                    delta = done - computed.get(id(r), 0)
                    if delta > 0:
                        fed.append((pos - delta, delta))
                        computed[id(r)] = done
                calls.append(Call(eng.name,
                                  "chunk" if eng.n_chunk_steps > c0
                                  else "decode", t0, t1, fed))
            return out
        return wrapped

    for name, eng in dep.engines():
        eng.step = engine_step(eng)
    srv = dep.server
    srv.step = _span("PoolServer.step", srv.step)
    srv.router.route_batch = _span("route_batch", srv.router.route_batch)
    srv.router.feedback_batch = _span("feedback_batch",
                                      srv.router.feedback_batch)
    for name in ("_featurize_score_device", "_featurize_score_host"):
        _capturing(srv.router, name, routes)
    # the state copies compile here, not at the window's first decision
    jax.block_until_ready(_router_state(
        srv.router, srv.router._device_featurize_active()))


# -- the open loop ---------------------------------------------------------------


class Stamper:
    """Finds the program's Request of each sent query and stamps its new
    tokens when a step returns."""

    def __init__(self, dep: Deployment, reqs: Dict[int, Req]):
        self.dep, self.reqs = dep, reqs
        self.active: Dict[int, Req] = {}

    def __call__(self) -> None:
        now = time.monotonic()
        for uid, request in self.dep.server.inflight.items():
            r = self.reqs.get(uid)
            if r is not None and r.request is None:
                r.request = request
                self.active[uid] = r
        for uid in list(self.active):
            r = self.active[uid]
            n = len(r.request.generated)
            if n > len(r.stamps):
                r.stamps.extend([now] * (n - len(r.stamps)))
            if r.request.done:
                del self.active[uid]


@dataclasses.dataclass
class TracePlan:
    start: float         # seconds after the window opens
    length: float
    directory: str = ""
    t_on: float = 0.0
    t_off: float = 0.0
    t_stopped: float = 0.0    # when stop_trace returned: the loop stalls
    anchors: List[Tuple[float, float]] = dataclasses.field(default_factory=list)


def _trace_on(plan: TracePlan) -> None:
    plan.directory = tempfile.mkdtemp(prefix="chipbench-trace-")
    jax.profiler.start_trace(plan.directory)
    for _ in range(3):
        a = time.monotonic()
        with jax.profiler.TraceAnnotation(SPAN + "anchor"):
            b = time.monotonic()
        plan.anchors.append((a, b))
    plan.t_on = plan.anchors[-1][1]


def _trace_off(plan: TracePlan) -> None:
    plan.t_off = time.monotonic()
    jax.profiler.stop_trace()
    plan.t_stopped = time.monotonic()


def drive(dep: Deployment, reqs: List[Req], t_open: float, warm_s: float,
          seconds: float, trace: Optional[TracePlan],
          routes: RouteCapture) -> Dict:
    """Send ``reqs`` at their due times, stepping the server between
    arrivals, and capture the window's routing decisions; returns the
    window and counter snapshots."""
    by_uid = {r.uid: r for r in reqs}
    stamp = Stamper(dep, by_uid)
    t0, t1 = t_open + warm_s, t_open + warm_s + seconds
    for r in reqs:
        r.due = t_open + r.arrival.due_s
    todo = [r for r in reqs if r.due < t1]
    i, lateness = 0, []
    snaps: Dict[str, Dict[str, float]] = {}
    while True:
        now = time.monotonic()
        if "start" not in snaps and now >= t0:
            snaps["start"] = dep.counters()
            routes.on = True
        if now >= t1:
            routes.on = False
            break
        while i < len(todo) and todo[i].due <= now:
            todo[i].sent = now
            lateness.append(now - todo[i].due)
            dep.server.enqueue(todo[i].query)
            i += 1
        if trace is not None:
            if not trace.t_on and now >= t0 + trace.start:
                _trace_on(trace)
            elif trace.t_on and not trace.t_off and (
                    now >= trace.t_on + trace.length):
                _trace_off(trace)
        if dep.busy():
            dep.server.step()
            stamp()
        else:
            nxt = todo[i].due if i < len(todo) else t1
            time.sleep(max(min(nxt, t1, t0 if now < t0 else t1) - now, 0.0))
    snaps["end"] = dep.counters()
    if trace is not None and trace.t_on and not trace.t_off:
        _trace_off(trace)
    for r in todo[i:]:        # due before the close, not yet sent
        r.sent = time.monotonic()
        lateness.append(r.sent - r.due)
        dep.server.enqueue(r.query)
    return {"window": (t0, t1), "counters": snaps, "lateness": lateness,
            "stamp": stamp}


def drain(dep: Deployment, reqs: List[Req], window, stamp: Stamper,
          limit_s: float) -> float:
    """After the close: serve what is left of the window's requests, up
    to ``limit_s``.  Returns the time the drain stopped."""
    t0, t1 = window
    waiting = [r for r in reqs if t0 <= r.due < t1 and r.sent is not None]
    deadline = t1 + limit_s
    while time.monotonic() < deadline and not all(r.done for r in waiting):
        dep.server.step()
        stamp()
    return time.monotonic()


# -- metrics ---------------------------------------------------------------------


def end_to_end(run: Run, setup_s: float, censor: float) -> Dict[str, float]:
    lo, hi = run.window
    mine = run.in_window()
    out = {"setup_s": setup_s}
    if mine:
        out["ttft_p90_ms"] = 1e3 * latency.ttft_tail(
            [(r.due, r.stamps[0] if r.stamps else None, censor)
             for r in mine], 0.90)
    gaps = [g for r in run.requests for g in latency.gaps_in(r.stamps, lo, hi)]
    if gaps:
        out["tbt_p95_ms"] = 1e3 * latency.nearest_rank(gaps, 0.95)
    out["output_tokens_per_s"] = sum(
        latency.count_in(r.stamps, lo, hi) for r in run.requests) / (hi - lo)
    return out


def _reduce_trace(plan: TracePlan) -> Optional[dict]:
    """Trace-time summary of the traced slice; ``None`` if it holds no
    device line (a CPU run)."""
    files = glob.glob(f"{plan.directory}/**/*.xplane.pb", recursive=True)
    if not files:
        return None
    tr = trace_reduce.read(files[0])
    shutil.rmtree(plan.directory, ignore_errors=True)
    anchors = [s for s in tr.spans if s.name == "anchor"]
    if not tr.ops and not tr.programs or len(anchors) != len(plan.anchors):
        return None
    # monotonic -> trace clock, from the anchor spans' midpoints
    offs = sorted((s.start + s.end) / 2 - (a + b) / 2
                  for s, (a, b) in zip(anchors, plan.anchors))
    off = offs[len(offs) // 2]
    lo, hi = plan.t_on + off, plan.t_off + off
    planes = sorted(set(tr.ops) | set(tr.programs))
    devices = {}
    for plane in planes:
        ops = tr.ops.get(plane) or tr.programs.get(plane, [])
        devices[plane] = {
            "busy_s": trace_reduce.union(ops, lo, hi),
            "programs": trace_reduce.program_times(
                tr.programs.get(plane, []), lo, hi),
            "idle": trace_reduce.idle_gaps(ops, tr.spans, lo, hi),
            "runs": [i for i in tr.programs.get(plane, [])
                     if lo <= i.start and i.end <= hi],
        }
    return {"lo": lo, "hi": hi, "offset": off, "window_s": hi - lo,
            "devices": devices, "spans": tr.spans}


def breakdown(trace: dict) -> dict:
    n = max(len(trace["devices"]), 1)
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for d in trace["devices"].values():
        for name, (_, s) in d["programs"].items():
            ops[name] = ops.get(name, 0.0) + s / n
        for name, s in trace_reduce.by_label(d["idle"]).items():
            idle[name] = idle.get(name, 0.0) + s / n
    return {"device_ops": trace_reduce.top(ops),
            "idle_gaps": trace_reduce.top(idle)}


# -- one run ---------------------------------------------------------------------


def build(cell: Cell, seed: int, stream: List[mix.Arrival], device
          ) -> Tuple[Dict[str, dict], Deployment, List[Req]]:
    """The weights drawn from ``seed`` on ``device``, the deployment on
    them, the stream's requests, and every program those reach compiled."""
    cfg = cell.config
    weights = {m["name"]: weights_lib.make(m["config"], seed, i, device)
               for i, m in enumerate(cfg["models"])}
    jax.block_until_ready(weights)
    for m in cfg["models"]:
        check_layout(weights[m["name"]],
                     ModelConfig(name=m["name"], **m["config"]))
    by_uid = {a.uid: a for a in stream}
    tables = {m["name"]: m["accuracy"] for m in cfg["models"]}

    def accuracy(query, resp) -> float:
        return mix.feedback_accuracy(by_uid[query.uid],
                                     tables[resp.model_name])

    dep = Deployment(cell, weights, device, accuracy)
    reqs = [Req(arrival=a,
                query=Query(uid=a.uid, text=a.text, task=TaskType[a.task],
                            max_new_tokens=a.max_new_tokens),
                prompt=tok.encode(a.text)) for a in stream]
    warm_up(dep, [r.prompt for r in reqs], [r.arrival.text for r in reqs],
            RouterConfig(**cfg["router"]))
    return weights, dep, reqs


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices: list,
        t_process: float, controls: Tuple[str, ...] = ()) -> dict:
    """One run of ``cell``; returns the result line's object.  With
    ``controls`` ("int8", "fp8") the result also holds each control's
    readings and its verdict under the cell's limits (``calibrate.py``)."""
    cfg, load = cell.config, cell.load
    serving = cfg["serving"]
    if len(devices) != 1:
        raise ValueError(f"{cell.name}: one pool on {len(devices)} devices")
    device = devices[0]
    kind = device.device_kind
    peaks = peaks_lib.peaks(kind) if device.platform == "tpu" else None

    stream = mix.generate(load, seconds)
    weights, dep, reqs = build(cell, seed, stream, device)
    calls: List[Call] = []
    routes = RouteCapture()
    instrument(dep, calls, routes)
    log(f"set-up before the loop: {time.monotonic() - t_process:.3f} s")

    plan = (TracePlan(start=0.4 * seconds, length=min(3.0, 0.3 * seconds))
            if trace else None)
    loop = drive(dep, reqs, time.monotonic(), load["warm_s"], seconds, plan,
                 routes)
    t0, t1 = loop["window"]
    setup_s = t0 - t_process
    censor = drain(dep, reqs, loop["window"], loop["stamp"],
                   load["drain_limit_s"])
    run_rec = Run(cell=cell, peaks=peaks, chips=len(devices),
                  window=(t0, t1), requests=reqs, calls=calls,
                  counters=loop["counters"],
                  prefix_models=frozenset(
                      n for n, e in dep.engines()
                      if e.prefix_cache is not None),
                  planes=frozenset({plane_name(device)}))
    late = loop["lateness"]
    log(f"generator lateness: sent {len(late)}, median "
        f"{1e3 * latency.nearest_rank(late, 0.5):.3f} ms, max "
        f"{1e3 * max(late):.3f} ms" if late else "generator sent nothing")
    win = run_rec.in_window()
    answered = [r for r in win if r.answered]
    failed = [r for r in win if not r.answered]
    log(f"window {seconds} s: due {sum(t0 <= r.due < t1 for r in reqs)}, "
        f"attempted {len(win)}, answered {len(answered)}, failed "
        f"{len(failed)}")
    c0, c1 = loop["counters"]["start"], loop["counters"]["end"]
    log(f"compiles inside the window: traces "
        f"{c1['traces'] - c0['traces']:.0f}, backend compiles "
        f"{c1['backend_compiles'] - c0['backend_compiles']:.0f}")
    served = {}
    for r in win:
        served[r.model] = served.get(r.model, 0) + 1
    log(f"routed in the window: {served}")

    device_rec = {"platform": device.platform, "kind": kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": (device.memory_stats() or {}).get(
                      "peak_bytes_in_use", 0)}
    if trace:
        run_rec.trace = _reduce_trace(plan) if plan.t_on \
            else None
        run_rec.stall = (plan.t_off, plan.t_stopped)
        log(f"profiler stop stalled the loop "
            f"{plan.t_stopped - plan.t_off:.3f} s")
        if run_rec.trace is not None:
            busy = [d["busy_s"] for p, d in run_rec.trace["devices"].items()
                    if p in run_rec.planes]
            if busy:
                device_rec["busy_s"] = sum(busy) / len(busy)
                device_rec["window_s"] = run_rec.trace["window_s"]
    metrics: Dict[str, dict] = {}
    if trace:
        for m in cell.per_layer:
            value = layer_reader(m["name"])(run_rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(run_rec, setup_s, censor)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    # correctness, once the program's state is freed
    finished = [r for r in win if r.answered]
    dep.free()
    del dep
    gc.collect()
    picked = check.sample(finished, cfg["check"]["requests_per_model"], seed)
    gaps = check.widest_gaps(picked, weights, cell.models,
                             length=serving["max_len"],
                             n_out=max(t["max_new_tokens"]
                                       for t in load["mix"]["tasks"]),
                             batch=cfg["check"]["batch"], controls=controls)
    log(f"compared {sum(g['tokens'] for g in gaps.values())} served tokens "
        f"of {len(picked)} requests, {sum(r.prefix_reused > 0 for r in picked)}"
        f" of them spliced ({sum(r.prefix_reused > 0 for r in finished)} of "
        f"{len(finished)} finished)")
    gaps[check.ROUTER] = route_ref.compare(
        [dict(c, state={k: np.asarray(v) for k, v in c["state"].items()})
         for c in routes.calls], cfg["router"], len(cfg["models"]),
        controls=(check.ROUTER_CONTROL,) if controls else ())
    log(f"router: {gaps[check.ROUTER]['compared']:.0f} decisions of the "
        f"window compared, {gaps[check.ROUTER]['unposed']:.0f} near-ties "
        f"left out")
    limits = cfg["check"]["limits"]
    checks = check.verdict(check.numbers(gaps), limits)
    correct = (not failed and gaps[check.ROUTER]["compared"] > 0
               and check.passes(checks))
    result = {"correct": bool(correct), "attempted": len(win),
              "failed": len(failed), "metrics": metrics, "device": device_rec}
    if trace and run_rec.trace is not None:
        result["breakdown"] = breakdown(run_rec.trace)
    if controls:
        result["controls"] = {}
        for fmt in controls:
            ctl = check.verdict(check.control_numbers(gaps, fmt), limits)
            result["controls"][fmt] = {"correct": check.passes(ctl),
                                       "checks": ctl}
    result["checks"] = checks
    return result
