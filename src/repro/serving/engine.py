"""Per-model serving engines with slot-based continuous batching.

Two backends behind one interface:

  * ``ModelEngine``  — a real JAX model (reduced config on CPU, full config
    on TPU) over a (max_batch,)-slot KV/state cache with *per-slot
    lengths*.  Prompts run as **chunked prefill**: each engine tick feeds
    every prefilling slot up to ``prefill_chunk`` prompt tokens through one
    jitted chunk-step (in-flight decode slots ride along with one token
    each, so continuous batching never stalls), then greedy decode runs
    one token per tick through the cheaper jitted ``serve_step`` until
    EOS/max_new_tokens.  A long prompt therefore reaches its first token
    in ~len/chunk ticks instead of len ticks.  Architectures whose state
    can't take a slab at an offset (recurrent rwkv/mamba, ring-buffer
    windowed caches) transparently fall back to one prompt token per tick
    — see ``api.supports_chunked_prefill``.
  * ``SimEngine``    — a timing/energy/accuracy model of a pool member
    (paper's 16-model pool has no public weights in this container); used
    by the paper-scale benchmarks.

Both report per-query energy (Wh) via the analytic TPU model (core.energy)
— the zeus stand-in of DESIGN §4 — and time-resolved per-step joules,
split by phase (prefill is compute-bound, decode bandwidth-bound; the
telemetry layer tags and charges the two separately).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import for_mode
from repro.core.energy import (CostModelParams, EnergyMonitor, JOULES_PER_WH,
                               chunk_rider_cost, decode_step_cost,
                               energy_joules, kv_migration_cost,
                               prefill_chunk_cost, prefill_cost, roofline)
from repro.core.types import ModelProfile
from repro.models import api
from repro.models.config import ModelConfig
from repro.serving.request import Request, RequestState, Response
from repro.telemetry import spans


class EngineFailure(RuntimeError):
    pass


# admission-time slot reset, in place (the cache is donated)
_reset_slot = jax.jit(api.reset_slot, donate_argnums=(0,))


# The engine's two tick programs, one compiled program per (config, shape):
# engines of one model share them, and they can be lowered from shapes
# alone (tests/test_tpu_compile.py compiles them for a described TPU).
@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def greedy_step(params, cache, tokens, cfg: ModelConfig):
    """One-token tick over every slot: (greedy next token (B,), cache)."""
    logits, cache = api.serve_step(params, tokens, cache, cfg)
    return jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32), cache


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def greedy_chunk_step(params, cache, tokens, n_active, cfg: ModelConfig):
    """Chunked-prefill tick: (B, C) slab, ``n_active`` real tokens per
    slot; the next token per slot comes from its last active position."""
    logits, cache = api.prefill_chunk(params, tokens, cache, cfg, n_active)
    idx = jnp.maximum(n_active - 1, 0)
    last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)
    return jnp.argmax(last[:, 0], axis=-1).astype(jnp.int32), cache


def engine_profile(name: str, cfg: ModelConfig) -> ModelProfile:
    """The pool profile of a ``ModelEngine`` serving ``cfg`` — what a
    router replica is built from before its engines exist (fleet shards)."""
    return ModelProfile(name=name, family=cfg.layout,
                        params_b=cfg.param_count() / 1e9, arch_config=cfg)


class BaseEngine:
    """Interface shared by real and simulated engines."""

    name: str
    profile: ModelProfile
    role: str = "unified"    # "prefill" | "decode" | "unified"

    def submit(self, req: Request) -> None:
        """Enqueue one routed request (admitted into a slot on a later step)."""
        raise NotImplementedError

    def submit_many(self, reqs: List[Request]) -> None:
        """Enqueue an already-routed batch slice in arrival order."""
        for req in reqs:
            self.submit(req)

    def step(self) -> List[Response]:
        """Advance the engine one tick; returns requests finished this tick."""
        raise NotImplementedError

    @property
    def pending(self) -> int:
        """Queued + in-slot request count (the scheduler's load signal)."""
        raise NotImplementedError

    @property
    def free_capacity(self) -> int:
        """Slots the engine could admit into right now (continuous-batching
        admission signal; 0 = saturated)."""
        return max(0, 1 - self.pending)

    # -- prefill/decode disaggregation hooks ----------------------------------

    def set_role(self, role: str) -> None:
        """Specialize the engine: a ``prefill`` engine hands requests off at
        the phase boundary instead of decoding them; a ``decode`` engine
        accepts those migrations.  Engines that can't export/import KV
        (no full-depth positional cache) silently stay ``unified``."""

    def drain_migrations(self) -> List[Request]:
        """Requests that finished prefill this tick and carry a KV payload;
        the scheduler moves them to the decode twin.  Draining empties the
        outbox.  Always empty for unified/decode roles."""
        return []

    def submit_migrated(self, req: Request) -> None:
        """Enqueue a migrated request (``req.kv_payload`` holds its prompt
        KV); spliced into a slot on a later step, then decoded."""
        raise NotImplementedError(f"{self.name} cannot accept migrations")

    def modeled_time_s(self) -> float:
        """Cumulative modeled wall-clock seconds of engine compute (per-tick
        roofline ``t_step`` summed).  Virtual-clock benches diff this to
        advance time by what the hardware would actually take — it is how
        prefill/decode interference (chunk-padded mixed ticks) becomes
        visible as TBT/TTFT inflation.  0.0 for engines without a roofline
        model (SimEngine)."""
        return 0.0

    def set_prefill_chunk(self, n: int) -> None:
        """Prompt tokens consumed per prefill tick (1 = token-wise legacy
        path).  No-op for engines without a real prefill (SimEngine)."""

    def set_prefix_cache(self, cache) -> None:
        """Attach a ``repro.cache.PrefixCache`` for cross-query prompt-KV
        reuse.  No-op for engines without a materialized KV cache
        (SimEngine) — real engines override and gate on layout support."""

    # -- telemetry hooks -------------------------------------------------------

    def cumulative_joules(self) -> float:
        """Cumulative metered energy in joules; sampled per scheduler step
        by the telemetry PowerTrace to derive a watts time-series."""
        return 0.0

    def cumulative_joules_by_phase(self) -> Dict[str, float]:
        """Cumulative metered joules split by serving phase ("prefill" /
        "decode"); the values sum to ``cumulative_joules()``.  Engines
        without a phase split report everything as decode."""
        return {"prefill": 0.0, "decode": self.cumulative_joules()}

    def cumulative_joules_avoided(self) -> float:
        """Cumulative modeled joules *not* spent thanks to prefix-KV
        reuse (the prefill work a spliced prefix replaced).  Telemetry
        diffs this per step into the avoided-energy counters, exactly as
        it does the phase joules."""
        return 0.0

    def prefix_hit_count(self) -> int:
        """Admissions that spliced a cached prefix (telemetry diffs it)."""
        return 0

    # -- fault-tolerance hooks -------------------------------------------------

    def heartbeat(self) -> float:
        """Monotonic seconds timestamp of the last completed step."""
        return getattr(self, "_last_step_s", 0.0)

    def inject_failure(self) -> None:
        self._failed = True

    def restart(self) -> List[Request]:
        """Reset engine state; returns in-flight requests for re-queueing."""
        raise NotImplementedError


class ModelEngine(BaseEngine):
    """Real-model engine: continuous batching over a slotted cache.

    ``prefill_chunk`` sets how many prompt tokens each prefilling slot
    consumes per tick (1 = token-wise path; the launcher default is 8).
    The chunked path applies only to layouts with a full-depth positional
    KV cache (``api.supports_chunked_prefill`` + a ``k`` cache entry —
    ring-buffer windowed caches are excluded); everything else silently
    clamps to 1.

    Weights are stored in bf16 (``configs.for_mode(cfg, "serve")``).  With
    ``device`` set, params and cache are committed to that device, so every
    tick runs there (a fleet shard's replica on its own chip).

    Each tick records ``engine.*:<name>`` spans into ``tracer`` (default
    ``spans.TRACER``): the tick, its admission, packing, the jitted call
    (``engine.dispatch.chunk`` / ``.decode``), the token read-back, the
    per-slot advance with one ``engine.sync.length`` per finish test, the
    prefix capture and the energy meter.
    """

    def __init__(self, name: str, cfg: ModelConfig, key: jax.Array,
                 max_batch: int = 4, max_len: int = 256,
                 params=None, detokenize: Optional[Callable] = None,
                 prefill_chunk: int = 1, role: str = "unified",
                 device: Optional[jax.Device] = None,
                 tracer: Optional[spans.Tracer] = None):
        self.name = name
        self.tracer = tracer or spans.TRACER
        self._span = spans.engine_span_names(self.tracer, name)
        self.cfg = dataclasses.replace(for_mode(cfg, "serve"),
                                       kv_update="where")
        self.max_batch = max_batch
        self.max_len = max_len
        self.device = device
        if params is None:
            with jax.default_device(device):
                params = api.init_params(self.cfg, key)
        self.params = self._place(params)
        self.cache = self._new_cache()
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.queue: List[Request] = []
        self.detokenize = detokenize or (lambda toks: "")
        self._failed = False
        self._last_step_s = time.monotonic()
        self.energy = EnergyMonitor()
        # per-step metered joules by serving phase (telemetry reads these)
        self._phase_joules = {"prefill": 0.0, "decode": 0.0}
        self.cost_params = CostModelParams(
            n_params=float(cfg.param_count()),
            n_active_params=float(cfg.active_param_count()),
            d_model=cfg.d_model, n_layers=cfg.n_layers,
            kv_heads=max(cfg.n_kv_heads, 1), head_dim=cfg.head_dim)
        self.profile = engine_profile(name, cfg)
        self.n_steps = 0
        self.n_chunk_steps = 0

        self._jit_step = functools.partial(greedy_step, cfg=self.cfg)
        self._jit_chunk_step = None
        self.prefill_chunk = 1
        self.set_prefill_chunk(prefill_chunk)
        # cross-query prefix-KV reuse (repro.cache): attached by the
        # scheduler's _configure_engine; None = recompute every prompt
        self.prefix_cache = None
        self._avoided_joules = 0.0
        self._prefix_hits = 0
        # prefill/decode disaggregation (docs/SERVING.md): a "prefill"
        # engine parks phase-boundary requests here for the scheduler to
        # move to the decode twin
        self.role = "unified"
        self._migration_outbox: List[Request] = []
        self._migration_joules = 0.0
        self._modeled_time_s = 0.0
        self.set_role(role)

    def _place(self, tree):
        """Commit ``tree`` to the engine's device (no-op without one)."""
        return tree if self.device is None else jax.device_put(tree,
                                                               self.device)

    def _new_cache(self):
        with jax.default_device(self.device):
            return self._place(api.init_cache(self.cfg, self.max_batch,
                                              self.max_len))

    def warmup(self) -> float:
        """Compile every tick program before the engine takes traffic;
        returns the seconds it took.  Each jitted step runs once over idle
        slots, then the cache is rebuilt, so no state survives."""
        t0 = time.perf_counter()
        cache = _reset_slot(self.cache, 0)
        tok, cache = self._jit_step(
            self.params, cache, jnp.zeros((self.max_batch, 1), jnp.int32))
        if self._jit_chunk_step is not None:
            tok, cache = self._jit_chunk_step(
                self.params, cache,
                jnp.zeros((self.max_batch, self.prefill_chunk), jnp.int32),
                jnp.zeros((self.max_batch,), jnp.int32))
        jax.block_until_ready(tok)
        del cache
        self.cache = self._new_cache()
        self._last_step_s = time.monotonic()
        return time.perf_counter() - t0

    def set_role(self, role: str) -> None:
        """Pin the engine to one serving phase.  The same full-depth
        positional-KV gate as chunked prefill applies: KV migration rides
        device→host ``_capture_prefix``-style copies and ``splice_prefix``,
        so recurrent (rwkv/mamba) and ring-buffer layouts silently fall
        back to ``unified`` and keep both phases local."""
        if role not in ("prefill", "decode", "unified"):
            raise ValueError(f"unknown engine role {role!r}")
        if role != "unified" and not (api.supports_chunked_prefill(self.cfg)
                                      and "k" in self.cache):
            role = "unified"
        self.role = role

    def set_prefill_chunk(self, n: int) -> None:
        """Set the prompt tokens consumed per prefill tick and (re)build
        the jitted chunk-step.  Clamped to 1 when the architecture can't
        take a slab at an offset (recurrent state, ring-buffer caches)."""
        n = max(int(n), 1)
        if not (api.supports_chunked_prefill(self.cfg) and "k" in self.cache):
            n = 1
        if n == self.prefill_chunk:
            return      # keep the warmed jit cache (hot-add re-push path)
        self.prefill_chunk = n
        self._jit_chunk_step = (
            None if n == 1 else functools.partial(greedy_chunk_step,
                                                  cfg=self.cfg))

    def set_prefix_cache(self, cache) -> None:
        """Attach (or detach, with None) a prefix-KV cache.  Only layouts
        whose decode cache can take a spliced slab participate — the same
        full-depth positional-KV gate as chunked prefill; ring-buffer and
        recurrent layouts silently keep recomputing their prompts."""
        if cache is not None and not (api.supports_chunked_prefill(self.cfg)
                                      and "k" in self.cache):
            cache = None
        self.prefix_cache = cache

    def cumulative_joules_avoided(self) -> float:
        return self._avoided_joules

    def prefix_hit_count(self) -> int:
        return self._prefix_hits

    def _prefill_joules(self, n_tokens: int, kv_start: int = 0) -> float:
        """Modeled joules the engine would spend prefilling ``n_tokens``
        prompt tokens starting at cache offset ``kv_start`` at its current
        chunk setting (mirrors ``_meter_step``'s charging rule: slabs > 1
        token cost ``prefill_chunk_cost``, single tokens
        ``decode_step_cost``).  With kv_start=0 this is the exact work a
        spliced prefix of that length avoids; with kv_start=p it is the
        work the uncached suffix still costs."""
        C = max(self.prefill_chunk, 1)
        joules, kv = 0.0, kv_start
        end = kv_start + n_tokens
        while kv < end:
            n = min(C, end - kv)
            if n > 1:
                f, b = prefill_chunk_cost(self.cost_params, n, kv)
            else:
                f, b = decode_step_cost(self.cost_params, max(kv + n, 1))
            joules += energy_joules(roofline(f, b, 0.0, self.energy.chips))
            kv += n
        return joules

    def estimate_prefill_wh(self, n_tokens: int) -> float:
        """Expected Wh saved by an ``n_tokens`` prefix hit (router-discount
        and governor-credit units)."""
        return self._prefill_joules(n_tokens) / JOULES_PER_WH

    # -- queueing ----------------------------------------------------------------

    def submit(self, req: Request) -> None:
        req.model_name = self.name
        self.queue.append(req)

    def submit_migrated(self, req: Request) -> None:
        """Accept a phase-boundary migration from the prefill twin.  The
        request keeps its prompt cursor (fully fed), its first generated
        token, and its stamped ``prefill_wh``; ``_admit`` splices the KV
        payload into a slot and decode continues from there."""
        self.queue.append(req)

    def drain_migrations(self) -> List[Request]:
        out, self._migration_outbox = self._migration_outbox, []
        return out

    def modeled_time_s(self) -> float:
        return self._modeled_time_s

    @property
    def pending(self) -> int:
        return (len(self.queue) + len(self._migration_outbox)
                + sum(s is not None for s in self.slots))

    @property
    def free_capacity(self) -> int:
        return max(0, self.max_batch - len(self.queue)
                   - sum(s is not None for s in self.slots))

    def _admit(self) -> None:
        for i in range(self.max_batch):
            if self.slots[i] is None and self.queue:
                req = self.queue.pop(0)
                if req.defunct:
                    continue
                req.slot = i
                self.slots[i] = req
                # zero the slot (length and recurrent state) so the
                # request starts fresh
                self.cache = _reset_slot(self.cache, i)
                if req.kv_payload is not None:
                    self._splice_migration(i, req)
                    continue
                req.state = RequestState.PREFILL
                req.start_s = time.monotonic()
                if self.prefix_cache is not None:
                    self._splice_prefix(i, req)

    def _splice_migration(self, slot: int, req: Request) -> None:
        """Land a migrated request: splice its carried prompt KV into the
        slot (cache length = prompt length, exactly the state the prefill
        twin left behind) and charge the migration DMA to the prefill
        ledger — it is phase-boundary overhead, not decode work."""
        k_blk, v_blk = req.kv_payload
        self.cache = api.splice_prefix(self.cache, slot, k_blk, v_blk)
        req.kv_payload = None
        req.state = RequestState.DECODE
        f, b = kv_migration_cost(self.cost_params, req.kv_migrated)
        terms = roofline(f, b, 0.0, self.energy.chips)
        joules = energy_joules(terms)
        self._migration_joules += joules
        self._phase_joules["prefill"] += joules
        self._modeled_time_s += terms.t_step

    def _splice_prefix(self, slot: int, req: Request) -> None:
        """Reuse the longest cached KV prefix for a newly admitted prompt.

        Keeps >= 1 prompt token to feed (the final token's forward pass
        produces the first-generation logits), so the cap is
        ``len(prompt) - 1``.  The splice sets the slot length to the
        reused depth; prefill then continues from that offset, and the
        avoided prefill work is credited to the engine's avoided-joules
        ledger (telemetry turns it into ``kind="prefix"`` counters)."""
        p, k_blk, v_blk = self.prefix_cache.match(
            req.prompt_tokens, max_tokens=len(req.prompt_tokens) - 1)
        if p <= 0:
            return
        self.cache = api.splice_prefix(self.cache, slot, k_blk, v_blk)
        req.n_prompt_fed = p
        req.prefix_reused = p
        self._prefix_hits += 1
        self._avoided_joules += self._prefill_joules(p)

    # -- the continuous-batching step ---------------------------------------------

    def step(self) -> List[Response]:
        """One engine tick.  Runs the jitted chunk-step when any slot still
        has prompt tokens pending (and chunking is enabled/supported),
        otherwise the cheaper one-token decode step.  Returns the requests
        that finished this tick."""
        if self._failed:
            raise EngineFailure(f"engine {self.name} failed")
        tr, sn = self.tracer, self._span
        with tr.span(sn.tick):
            if self.queue:
                with tr.span(sn.admit):
                    self._admit()
            self._last_step_s = time.monotonic()
            if not any(self.slots):
                return []
            need_prefill = any(
                req is not None and not req.defunct
                and not req.prefill_done for req in self.slots)
            if self._jit_chunk_step is not None and need_prefill:
                out = self._chunk_tick()
            else:
                out = self._decode_tick()
            # the heartbeat is the end of the last completed tick: a tick
            # that spent a minute compiling still counts as progress
            self._last_step_s = time.monotonic()
            return out

    def _decode_tick(self) -> List[Response]:
        """Legacy one-token tick: every live slot feeds one token (next
        prompt token while prefilling, last generated token while
        decoding) through the jitted ``serve_step``."""
        tr, sn = self.tracer, self._span
        with tr.span(sn.pack):
            tokens = np.zeros((self.max_batch, 1), np.int32)
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                if not req.prefill_done:
                    tokens[i, 0] = req.prompt_tokens[req.n_prompt_fed]
                else:
                    tokens[i, 0] = (req.generated[-1] if req.generated
                                    else req.prompt_tokens[-1])
        with tr.span(sn.dispatch_decode):
            next_tok, self.cache = self._jit_step(self.params, self.cache,
                                                  jnp.asarray(tokens))
        with tr.span(sn.sync_tokens):
            next_tok = np.asarray(next_tok)
        self.n_steps += 1
        with tr.span(sn.meter):
            # token-wise prefill runs the decode kernel, so it costs a
            # decode step — but it is still prefill work, tagged as such
            self._meter_step([
                ("prefill" if not req.prefill_done else "decode", 1,
                 max(req.n_prompt_fed + len(req.generated), 1))
                for req in self.slots
                if req is not None and not req.defunct])
        fed_prompt = [0 if (req is None or req.prefill_done) else 1
                      for req in self.slots]
        with tr.span(sn.advance):
            return self._advance_slots(next_tok, fed_prompt)

    def _chunk_tick(self) -> List[Response]:
        """Chunked-prefill tick: prefilling slots consume up to
        ``prefill_chunk`` prompt tokens, decode slots ride along with one
        token each (continuous batching never stalls), all in one jitted
        chunk-step."""
        tr, sn = self.tracer, self._span
        C = self.prefill_chunk
        with tr.span(sn.pack):
            tokens = np.zeros((self.max_batch, C), np.int32)
            n_active = np.zeros((self.max_batch,), np.int32)
            fed_prompt = [0] * self.max_batch
            meter = []
            for i, req in enumerate(self.slots):
                if req is None or req.defunct:
                    continue
                kv_start = req.n_prompt_fed + len(req.generated)
                if not req.prefill_done:
                    n = min(C, len(req.prompt_tokens) - req.n_prompt_fed)
                    tokens[i, :n] = req.prompt_tokens[
                        req.n_prompt_fed:req.n_prompt_fed + n]
                    n_active[i] = n
                    fed_prompt[i] = n
                    meter.append(("prefill", n, kv_start))
                else:
                    tokens[i, 0] = (req.generated[-1] if req.generated
                                    else req.prompt_tokens[-1])
                    n_active[i] = 1
                    # decode rider in a mixed tick: its row is chunk-padded
                    # through the fused kernel (see chunk_rider_cost)
                    meter.append(("decode", 1, max(kv_start, 1), C))
        with tr.span(sn.dispatch_chunk):
            next_tok, self.cache = self._jit_chunk_step(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(n_active))
        with tr.span(sn.sync_tokens):
            next_tok = np.asarray(next_tok)
        self.n_steps += 1
        self.n_chunk_steps += 1
        with tr.span(sn.meter):
            self._meter_step(meter)
        with tr.span(sn.advance):
            return self._advance_slots(next_tok, fed_prompt)

    def _advance_slots(self, next_tok: np.ndarray,
                       fed_prompt: List[int]) -> List[Response]:
        """Shared post-step bookkeeping: advance prompt cursors, record
        TTFT at the first generated token, append decode tokens, finish
        on EOS / max_new_tokens / cache overflow.  On a ``prefill``-role
        engine, requests that survive the finish checks at the phase
        boundary are handed to the migration outbox instead of decoding
        locally."""
        finished: List[Response] = []
        now = time.monotonic()
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if req.defunct:
                self.slots[i] = None
                continue
            if fed_prompt[i]:
                req.n_prompt_fed += fed_prompt[i]
                if req.prefill_done:
                    req.state = RequestState.DECODE
                    req.generated.append(int(next_tok[i]))
                    req.first_token_s = now
                    # the first generated token gets the same finish checks
                    # as any decode token — an EOS-first or 1-token-budget
                    # request must not survive into decode (or migrate)
                    if self._should_finish(i, req):
                        finished.append(self._finish(i))
                    elif self.role == "prefill":
                        self._emit_migration(i, req)
                continue
            req.generated.append(int(next_tok[i]))
            if self._should_finish(i, req):
                finished.append(self._finish(i))
        return finished

    def _should_finish(self, slot: int, req: Request) -> bool:
        hit_eos = req.generated[-1] == req.eos_id
        full = len(req.generated) >= req.max_new_tokens
        with self.tracer.span(self._span.sync_length):
            overflow = int(self.cache["length"][slot]) >= self.max_len - 1
        return hit_eos or full or overflow

    def _emit_migration(self, slot: int, req: Request) -> None:
        """Phase boundary on a prefill-role engine: snapshot the prompt KV
        (device→host, the ``_capture_prefix`` transport), stamp the metered
        prefill-phase Wh on the request, and free the slot for the next
        arrival.  Prompts that overflowed the slot cache have unwritten KV
        positions — nothing trustworthy to ship — so they stay local and
        decode here (per-request unified fallback)."""
        n_p = len(req.prompt_tokens)
        if n_p > self.max_len - 1:
            return
        k = np.asarray(self.cache["k"][:, slot, :n_p])
        v = np.asarray(self.cache["v"][:, slot, :n_p])
        req.kv_payload = (k, v)
        req.kv_migrated = n_p
        req.prefill_wh = self._prefill_phase_wh(req)
        req.state = RequestState.MIGRATING
        req.slot = -1
        self._migration_outbox.append(req)
        self.slots[slot] = None

    def _prefill_phase_wh(self, req: Request) -> float:
        """The prefill share of the per-query Wh of record, stamped at
        migration time by the engine that actually did the work.  Mirrors
        ``_query_wh``'s split: cold prompts cost ``measure_query``'s
        prefill term, spliced prompts only their uncached suffix.  The
        spend is charged to this engine's monitor now — a later decode-twin
        failure re-queues the request but never un-spends these joules."""
        n_p = max(len(req.prompt_tokens), 1)
        if req.prefix_reused > 0:
            joules = self._prefill_joules(max(n_p - req.prefix_reused, 1),
                                          kv_start=req.prefix_reused)
        else:
            f, b = prefill_cost(self.cost_params, n_p)
            joules = energy_joules(roofline(f, b, 0.0, self.energy.chips))
        self.energy.total_joules += joules
        return joules / JOULES_PER_WH

    def _meter_step(self, fed) -> None:
        """Accumulate this tick's modeled energy from the analytic cost
        model, split by phase.  ``fed`` lists (phase, n_tokens, kv_len)
        per live slot — plus a 4th ``pad`` element for decode riders in
        mixed chunk ticks: prefill slabs are charged
        ``prefill_chunk_cost`` (one weight read amortized over the slab),
        plain decode tokens ``decode_step_cost``, and padded riders
        ``chunk_rider_cost`` (the fused chunk kernel computes all ``pad``
        positions of the rider's row — the interference cost
        role-specialized engines avoid).  This is the time-resolved
        counterpart of ``measure_query`` (which stays the per-query Wh
        accounting of record).  No device sync: kv lengths come from
        request progress, not the cache.

        The same per-slot terms also advance the modeled tick-time ledger:
        one roofline ``t_step`` over the tick's aggregate FLOPs/bytes,
        with the per-slot weight read collapsed to a single read — the
        batched kernel streams the weights once per tick, so per-slot
        energy charges keep the read (each slot's query really pays for
        it) while tick *time* must not multiply it."""
        tick_flops, tick_bytes = 0.0, 0.0
        w_bytes = self.cost_params.n_active_params * self.cost_params.dtype_bytes
        for entry in fed:
            phase, n_tokens, kv_len = entry[:3]
            pad = entry[3] if len(entry) > 3 else 0
            if phase == "prefill" and n_tokens > 1:
                f, b = prefill_chunk_cost(self.cost_params, n_tokens, kv_len)
            elif pad > 1:
                f, b = chunk_rider_cost(self.cost_params, pad, max(kv_len, 1))
            else:
                f, b = decode_step_cost(self.cost_params, max(kv_len, 1))
            self._phase_joules[phase] += energy_joules(
                roofline(f, b, 0.0, self.energy.chips))
            tick_flops += f
            tick_bytes += b - w_bytes
        if fed:
            tick_bytes += w_bytes
            self._modeled_time_s += roofline(
                tick_flops, max(tick_bytes, 0.0), 0.0,
                self.energy.chips).t_step

    def cumulative_joules(self) -> float:
        return self._phase_joules["prefill"] + self._phase_joules["decode"]

    def cumulative_joules_by_phase(self) -> Dict[str, float]:
        return dict(self._phase_joules)

    def _finish(self, slot: int) -> Response:
        req = self.slots[slot]
        self._capture_prefix(slot, req)
        self.slots[slot] = None
        req.state = RequestState.DONE
        req.finish_s = time.monotonic()
        out = [t for t in req.generated if t != req.eos_id]
        if req.kv_migrated and req.prefill_wh > 0:
            pre_wh, dec_wh = self._migrated_query_wh(req, len(out))
        else:
            pre_wh, dec_wh = self._query_wh(len(req.prompt_tokens),
                                            req.prefix_reused, len(out))
        ttft_ms = ((req.first_token_s - req.arrival_s) * 1e3
                   if req.first_token_s else 0.0)
        return Response(
            uid=req.uid, model_name=self.name, tokens=out,
            text=self.detokenize(out), latency_ms=req.latency_ms,
            queue_ms=(req.start_s - req.arrival_s) * 1e3,
            energy_wh=pre_wh + dec_wh, input_tokens=len(req.prompt_tokens),
            output_tokens=len(out), hedged_winner=req.hedged,
            ttft_ms=ttft_ms, prefix_reused=req.prefix_reused,
            kv_migrated=req.kv_migrated, prefill_wh=pre_wh)

    def _query_wh(self, n_prompt: int, reused: int,
                  n_out: int) -> tuple:
        """Per-query (prefill Wh, decode Wh) of record; the sum is what
        ``measure_query`` charges.  Cold queries keep its terms exactly.
        With a spliced prefix, the prefill term covers only the uncached
        suffix (charged at its true cache offsets) while decode is still
        charged at *full* context depth — prefix reuse avoids prefill
        work, never decode work (every decode step attends over the whole
        cache).  The bandit feedback and the governor's bucket drain both
        see this true spend; the phase split feeds the cost model's
        per-phase residuals."""
        if reused <= 0:
            f, b = prefill_cost(self.cost_params, max(n_prompt, 1))
            pre_j = energy_joules(roofline(f, b, 0.0, self.energy.chips))
        else:
            pre_j = self._prefill_joules(max(n_prompt - reused, 1),
                                         kv_start=reused)
        mid_kv = n_prompt + max(n_out, 1) // 2
        f, b = decode_step_cost(self.cost_params, mid_kv)
        dec_j = max(n_out, 0) * energy_joules(
            roofline(f, b, 0.0, self.energy.chips))
        # keep the monitor's totals coherent with measure_query's
        self.energy.total_joules += pre_j + dec_j
        self.energy.n_queries += 1
        return pre_j / JOULES_PER_WH, dec_j / JOULES_PER_WH

    def _migrated_query_wh(self, req: Request, n_out: int) -> tuple:
        """Per-query (prefill Wh, decode Wh) of record for a request that
        prefilled elsewhere: the prefill twin's stamped ``prefill_wh`` +
        the phase-boundary KV DMA on the prefill side, this engine's
        decode work at full context depth on the decode side.  Decode is
        charged here (mirroring ``_query_wh``'s mid-depth decode term);
        the prefill term was already charged to the twin's monitor at
        migration time."""
        n_prompt = len(req.prompt_tokens)
        mid_kv = n_prompt + max(n_out, 1) // 2
        f, b = decode_step_cost(self.cost_params, mid_kv)
        dec_j = max(n_out, 0) * energy_joules(
            roofline(f, b, 0.0, self.energy.chips))
        f, b = kv_migration_cost(self.cost_params, req.kv_migrated)
        mig_j = energy_joules(roofline(f, b, 0.0, self.energy.chips))
        self.energy.total_joules += dec_j + mig_j
        self.energy.n_queries += 1
        return (req.prefill_wh + mig_j / JOULES_PER_WH,
                dec_j / JOULES_PER_WH)

    def _capture_prefix(self, slot: int, req: Request) -> None:
        """Register a finished prompt's KV with the prefix cache.  The
        prompt region [0, n_prompt) of the slot cache is still intact at
        finish time (decode appends strictly after it), so the capture is
        one device→host copy; whole blocks only (tail rounding lives in
        ``PrefixCache.insert``)."""
        n_p = len(req.prompt_tokens)
        if (self.prefix_cache is None
                or n_p < self.prefix_cache.block_tokens
                or req.n_prompt_fed < n_p
                or n_p > self.max_len - 1):
            # the last guard: a prompt that overflowed the slot cache has
            # KV positions >= max_len that were never written — nothing
            # trustworthy to capture
            return
        tr, sn = self.tracer, self._span
        with tr.span(sn.capture):
            with tr.span(sn.sync_capture):
                k = np.asarray(self.cache["k"][:, slot, :n_p])
                v = np.asarray(self.cache["v"][:, slot, :n_p])
            self.prefix_cache.insert(req.prompt_tokens, k, v)

    def restart(self) -> List[Request]:
        # defunct (cancelled/timed-out/failed) requests are dropped, not
        # resurrected: resetting one to QUEUED would re-enter a terminal
        # uid into the scheduler's bookkeeping
        inflight = [r for r in ([r for r in self.slots if r is not None]
                                + self.queue + self._migration_outbox)
                    if not r.defunct]
        for r in inflight:
            r.state = RequestState.QUEUED
            r.slot = -1
            r.generated = []
            r.n_prompt_fed = 0
            r.prefix_reused = 0          # re-splices on re-admission
            r.first_token_s = 0.0
            # drop any in-transit KV: it is re-prefilled from scratch, and
            # the twin's already-charged joules stay spent (never refunded)
            r.kv_payload = None
            r.kv_migrated = 0
            r.prefill_wh = 0.0
        self.slots = [None] * self.max_batch
        self.queue = []
        self._migration_outbox = []
        self.cache = self._new_cache()
        self._failed = False
        return inflight


class SimEngine(BaseEngine):
    """Pool-member simulator: latency/energy/accuracy from profiles.

    Used by the paper-scale benchmarks (16 models × 2500 queries in
    seconds).  ``outcome_fn(query, model_name) -> (accuracy, energy_wh,
    latency_ms, out_tokens)`` encapsulates the calibrated behaviour tables
    (repro.data.profiles).

    A faithful cheap twin of ``ModelEngine`` for everything the scheduler,
    cache, cost model, and telemetry observe:

      * ``concurrency`` mirrors the slot semantics: up to that many queued
        requests make progress each step, and ``free_capacity`` reports
        the unused slots so ``PoolServer.enqueue`` continuous batching
        admits at the pool's real parallelism;
      * ``clock`` injects the time source (same pattern as
        ``SemanticCache.clock``) so ``start_s``/``finish_s``/heartbeats
        live on the bench's virtual clock instead of mixing wall and
        modeled time;
      * energy is phase-split: each completion's Wh divides into prefill
        vs decode by per-token weights mirroring the calibrated tables'
        marginal costs, feeding ``cumulative_joules_by_phase`` and
        ``Response.prefill_wh`` exactly like the real engine;
      * prefix-KV reuse is modeled with the *real* ``PrefixCache`` radix
        trie (token-level matching, LRU eviction) — a hit discounts the
        avoided prefill share from the query's spend and credits the
        avoided-joules ledger, never un-spending energy;
      * ``modeled_time_s`` advances by the slowest active request's
        per-step latency share, so virtual-clock benches can diff it.
    """

    # prefill/decode per-token Wh weights for the phase split; the ratio
    # mirrors repro.data.profiles' marginal costs (MWH_PER_B_PER_IN_TOKEN
    # vs MWH_PER_B_PER_OUT_TOKEN), kept literal to avoid a data-layer dep
    PREFILL_TOKEN_WEIGHT = 0.002
    DECODE_TOKEN_WEIGHT = 0.15

    def __init__(self, profile: ModelProfile, outcome_fn,
                 steps_per_query: int = 1, concurrency: int = 1,
                 clock: Optional[Callable[[], float]] = None):
        if concurrency < 1:
            raise ValueError(f"concurrency must be >= 1, got {concurrency}")
        self.name = profile.name
        self.profile = profile
        self.outcome_fn = outcome_fn
        self.queue: List[Request] = []
        self.steps_per_query = steps_per_query
        self.concurrency = concurrency
        self.clock = clock or time.monotonic
        self._failed = False
        self._last_step_s = self.clock()
        self._progress: Dict[int, int] = {}
        # outcome drawn once at first service step (slot activation) and
        # held until completion, so latency can pace the modeled clock
        self._outcomes: Dict[int, tuple] = {}
        self._phase_joules = {"prefill": 0.0, "decode": 0.0}
        self._modeled_time_s = 0.0
        self.prefix_cache = None
        self._avoided_joules = 0.0
        self._prefix_hits = 0
        # EWMA of observed cold prefill Wh per prompt token, backing
        # estimate_prefill_wh (honest: a cold engine offers no discount)
        self._prefill_wh_per_token: Optional[float] = None

    def submit(self, req: Request) -> None:
        req.model_name = self.name
        self.queue.append(req)

    @property
    def pending(self) -> int:
        return len(self.queue)

    @property
    def free_capacity(self) -> int:
        return max(0, self.concurrency - len(self.queue))

    def set_prefix_cache(self, cache) -> None:
        """Attach a PrefixCache; the sim models a full-depth positional KV
        layout, so there is no layout gate."""
        self.prefix_cache = cache

    def cumulative_joules(self) -> float:
        return self._phase_joules["prefill"] + self._phase_joules["decode"]

    def cumulative_joules_by_phase(self) -> Dict[str, float]:
        return dict(self._phase_joules)

    def cumulative_joules_avoided(self) -> float:
        return self._avoided_joules

    def prefix_hit_count(self) -> int:
        return self._prefix_hits

    def modeled_time_s(self) -> float:
        return self._modeled_time_s

    def estimate_prefill_wh(self, n_tokens: int) -> float:
        """Expected Wh an ``n_tokens`` prefix hit saves, from the observed
        per-token prefill EWMA (0 until the first completion calibrates it
        — a cold cache honestly offers no routing discount)."""
        return (self._prefill_wh_per_token or 0.0) * max(n_tokens, 0)

    def _phase_split(self, n_in: int, n_out: int) -> float:
        """Prefill fraction of a completion's energy, by token-weighted
        marginal cost (the fixed overhead splits proportionally)."""
        pre = max(n_in, 0) * self.PREFILL_TOKEN_WEIGHT
        dec = max(n_out, 1) * self.DECODE_TOKEN_WEIGHT
        return pre / max(pre + dec, 1e-12)

    def _activate(self, req: Request) -> tuple:
        """First service step for a request: stamp start, probe the prefix
        cache (the modeled splice — ``match`` LRU-touches the chain like a
        real admission), and draw + pin the outcome."""
        if req.start_s == 0.0:
            req.start_s = self.clock()
        outcome = self._outcomes.get(req.uid)
        if outcome is None:
            if (self.prefix_cache is not None and req.prefix_reused == 0
                    and len(req.prompt_tokens) > 1):
                p, _, _ = self.prefix_cache.match(
                    req.prompt_tokens, max_tokens=len(req.prompt_tokens) - 1)
                if p > 0:
                    req.prefix_reused = p
                    self._prefix_hits += 1
            # state stays QUEUED while in service (a sim "slot" has no
            # prefill/decode sub-lifecycle); hedging semantics match the
            # seed engine: a slow head-of-queue request is still hedgeable
            outcome = self.outcome_fn(req.query, self.name)
            self._outcomes[req.uid] = outcome
        return outcome

    def _finish(self, req: Request, outcome: tuple) -> Response:
        acc, energy_wh, latency_ms, out_tokens = outcome
        n_in = len(req.prompt_tokens)
        pre_frac = self._phase_split(n_in, out_tokens)
        pre_wh_cold = energy_wh * pre_frac
        dec_wh = energy_wh - pre_wh_cold
        avoided_wh = 0.0
        if req.prefix_reused > 0 and n_in > 0:
            # the spliced share of the prompt was never prefilled: its
            # energy is avoided (credited, not un-spent) and the query's
            # Wh of record covers only the uncached suffix + decode
            avoided_wh = pre_wh_cold * min(req.prefix_reused / n_in, 1.0)
            self._avoided_joules += avoided_wh * JOULES_PER_WH
        pre_wh = pre_wh_cold - avoided_wh
        self._phase_joules["prefill"] += pre_wh * JOULES_PER_WH
        self._phase_joules["decode"] += dec_wh * JOULES_PER_WH
        if n_in > 0:
            sample = pre_wh_cold / n_in
            self._prefill_wh_per_token = (
                sample if self._prefill_wh_per_token is None
                else 0.8 * self._prefill_wh_per_token + 0.2 * sample)
        if (self.prefix_cache is not None
                and n_in >= self.prefix_cache.block_tokens):
            # register the completed prompt; the sim has no real KV, so
            # placeholder blocks stand in (matching is token-exact either
            # way, and capacity/eviction behave like the real pool)
            kv = np.zeros((1, n_in, 1, 1), np.float32)
            self.prefix_cache.insert(req.prompt_tokens, kv, kv)
        req.state = RequestState.DONE
        req.finish_s = self.clock()
        resp = Response(
            uid=req.uid, model_name=self.name, tokens=[], text="",
            latency_ms=latency_ms,
            queue_ms=(req.start_s - req.arrival_s) * 1e3,
            energy_wh=pre_wh + dec_wh,
            input_tokens=n_in,
            output_tokens=out_tokens, ttft_ms=latency_ms,
            prefix_reused=req.prefix_reused, prefill_wh=pre_wh)
        resp.accuracy = acc  # type: ignore[attr-defined]
        return resp

    def step(self) -> List[Response]:
        if self._failed:
            raise EngineFailure(f"engine {self.name} failed")
        self._last_step_s = self.clock()
        out: List[Response] = []
        if not self.queue:
            return out
        keep: List[Request] = []
        active = 0
        tick_dt = 0.0
        for pos, req in enumerate(self.queue):
            if active >= self.concurrency:
                keep.extend(self.queue[pos:])
                break
            if req.defunct:
                self._progress.pop(req.uid, None)
                self._outcomes.pop(req.uid, None)
                continue                       # drop; frees its slot
            active += 1
            outcome = self._activate(req)
            # the tick takes as long as its slowest active request's
            # per-step share (slots run concurrently, like real slots)
            tick_dt = max(tick_dt,
                          outcome[2] / 1e3 / max(self.steps_per_query, 1))
            k = self._progress.get(req.uid, 0) + 1
            if k < self.steps_per_query:
                self._progress[req.uid] = k
                keep.append(req)
                continue
            self._progress.pop(req.uid, None)
            self._outcomes.pop(req.uid, None)
            out.append(self._finish(req, outcome))
        self.queue = keep
        self._modeled_time_s += tick_dt
        return out

    def restart(self) -> List[Request]:
        # like ModelEngine.restart: defunct requests are dropped, never
        # resurrected into the scheduler's bookkeeping
        inflight = [r for r in self.queue if not r.defunct]
        for r in inflight:
            r.state = RequestState.QUEUED
            r.start_s = 0.0
            r.prefix_reused = 0          # re-probes on re-admission
        self.queue = []
        self._progress.clear()
        self._outcomes.clear()
        self._failed = False
        return inflight
