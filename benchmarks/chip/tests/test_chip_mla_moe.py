"""DeepSeek-V2-Lite's cell (``pool2-dsv2lite.paper-mix.steady``) and its
architecture module ``arch/mla_moe.py``: the cell is found by name, the
module's tree is the program's at the published widths with the counted
parameters, and at a tiny size on the CPU the program's served path
(chunked prefill, latent prefix splices and decoding through the latent
cache, the absorbed attention, the YaRN rope, the expert layer at a share)
agrees with the module's float32 reference."""
import dataclasses
import json
import math
import pathlib
import shutil
import sys
import time
import types

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import flops  # noqa: E402
import harness  # noqa: E402
import layouts  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
import weights  # noqa: E402
from repro.configs import for_mode  # noqa: E402
from repro.models import api, mla, moe  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.telemetry import spans  # noqa: E402

CELL = "pool2-dsv2lite.paper-mix.steady"
DS = "deepseek-v2-lite"
MLA = layouts.module("mla_moe")


def published():
    cfg = json.loads((HERE / "configs/pool2-dsv2lite-rwkv1.6b.json")
                     .read_text())
    return cfg, {m["name"]: m["config"] for m in cfg["models"]}[DS]


# a tiny DeepSeek: one dense layer and two expert layers, 16 experts of
# which this share holds 4..7, 4 per token from a softmax over all 16
TINY = dict(n_layers=3, d_model=64, n_heads=4, d_ff=96, vocab_size=256,
            n_experts=16, top_k=4, moe_d_ff=32, experts_first=4,
            experts_held=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, yarn_original_len=64)


def tiny(**over):
    return dict(published()[1], **dict(TINY, **over))


def program_cfg(cfg):
    """The program's config of the same model, computing in float32 so
    that it meets the float32 reference to rounding."""
    return dataclasses.replace(for_mode(ModelConfig(name=DS, **cfg), "serve"),
                               dtype="float32", kv_update="where")


def ref_logits(params, cfg, seq):
    a = reference.Arch.of(cfg)
    hid = MLA.hidden(params, jnp.asarray([seq]), a)
    return np.asarray(hid[0] @ MLA.head(params).astype(jnp.float32))


# -- the cell and the published tree --------------------------------------------


def test_the_new_cell_is_found_by_name():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and set(cell.models) == {DS, "rwkv6-1.6b"}
    assert cell.models[DS]["layout"] == "mla_moe"
    assert cell.load["rate_qps"] > 0 and cell.load["stream_seed"] == 3
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "ttft_p90_ms", "tbt_p95_ms", "output_tokens_per_s"}
    names = {m["name"] for m in cell.per_layer}
    assert "greedy_step_roofline.mla_moe" in names
    for name in names:
        assert callable(harness.layer_reader(name))


def test_the_published_tree_is_the_programs_and_its_count():
    cfg, ds = published()
    assert cfg["n_routed_experts"] == ds["experts_held"] == 8
    assert cfg["n_routed_experts_published"] == ds["n_experts"] == 64
    specs = tuple(weights.leaf_specs(ds))
    tree = jax.eval_shape(weights._generate, jax.random.PRNGKey(0), specs)
    harness.check_layout(tree, ModelConfig(name=DS, **ds))
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(tree))
    assert n == sum(flops.param_count(ds).values()) == 3_110_989_312
    assert n == ModelConfig(name=DS, **ds).param_count()
    # the uncut model is DeepSeek-V2-Lite's 15.7e9
    assert ModelConfig(name=DS, **dict(ds, experts_held=0)).param_count() \
        == 15_706_484_224


def test_counts_at_the_published_widths():
    ds = published()[1]
    # q 2048x16x192, latent + rope key 2048x576, up 512x16x256, out
    # 16x128x2048; gate 2048x64; 2 shared + 0.75 held experts of 3x2048x1408
    attn = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert MLA.held_passes(ds) == 0.75
    assert flops.layer_matmul_params(ds) == attn + 131_072 + 2.75 * 8_650_752
    # one token at position 0: layer 0's attention and 3x2048x10944 MLP,
    # 26 expert layers, attention over one row (2 x 16 x (576 + 512)) in
    # all 27, and the 2048 x 102400 head
    assert flops.token_flops(ds, 0) == (
        2 * (attn + 67_239_936) + 2 * 26 * (attn + 131_072 + 2.75 * 8_650_752)
        + 27 * 34_816 + 2 * 2048 * 102400)
    assert flops.token_flops(ds, 9) - flops.token_flops(ds, 0) == \
        27 * 34_816 * 9
    # one 576-wide bf16 row per position per layer, read and one written
    assert flops.state_bytes(ds, 0) == 27 * 576 * 2
    assert flops.state_bytes(ds, 99) == 27 * 576 * 2 * 100
    # every held expert is read once per call, whatever the batch
    held_layer = attn + 131_072 + 10 * 8_650_752
    assert flops.weight_bytes(ds) == 2 * (
        attn + 67_239_936 + 26 * held_layer + 27 * (2 * 2048 + 512)
        + 2048 * 102400 + 2048)


# -- the program against the reference, tiny, on the CPU ---------------------------


def _chunked(params, mc, prompts, cache, fed, chunk=8):
    """Feed the prompts from ``fed`` on, ``chunk`` tokens a tick, every
    slot its own count; returns each slot's last prompt logits."""
    b = len(prompts)
    last = [None] * b
    while any(fed[i] < len(p) for i, p in enumerate(prompts)):
        toks = np.zeros((b, chunk), np.int32)
        n_active = np.zeros((b,), np.int32)
        for i, p in enumerate(prompts):
            n = min(chunk, len(p) - fed[i])
            toks[i, :n] = p[fed[i]:fed[i] + n]
            n_active[i] = n
        logits, cache = api.prefill_chunk(params, jnp.asarray(toks), cache,
                                          mc, jnp.asarray(n_active))
        for i in range(b):
            if n_active[i] and fed[i] + n_active[i] == len(prompts[i]):
                last[i] = np.asarray(logits[i, n_active[i] - 1])
            fed[i] += n_active[i]
    return last, cache


def _decode(params, mc, prompts, last, cache, steps=5):
    """Greedy decode from ``last``; returns each slot's (sequence, logits
    of every served position)."""
    seqs = [list(p) for p in prompts]
    outs = [[l] for l in last]
    for _ in range(steps):
        tok = np.array([[int(np.argmax(o[-1]))] for o in outs], np.int32)
        logits, cache = api.serve_step(params, jnp.asarray(tok), cache, mc)
        for i in range(len(prompts)):
            seqs[i].append(int(tok[i, 0]))
            outs[i].append(np.asarray(logits[i, 0]))
    return [(s, np.stack(o)) for s, o in zip(seqs, outs)]


def _assert_matches(params, cfg, prompts, served):
    for p, (seq, got) in zip(prompts, served):
        ref = ref_logits(params, cfg, seq)[len(p) - 1:]
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=0)


def test_chunked_prefill_then_decode_matches_the_reference():
    """Ragged slabs of 8 (13, 21 and 5 prompt tokens), then five tokens
    decoded through the latent cache: every logit within float32 rounding
    of the reference's full forward pass."""
    cfg = tiny()
    params = weights.make(cfg, 5, 0, jax.devices()[0])
    mc = program_cfg(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist() for n in (13, 21, 5)]
    cache = api.init_cache(mc, 3, 48)
    assert set(cache) == {"k", "length"}
    assert cache["k"].shape == (3, 3, 48, 40)
    last, cache = _chunked(params, mc, prompts, cache, [0, 0, 0])
    _assert_matches(params, cfg, prompts,
                    _decode(params, mc, prompts, last, cache))


def test_a_spliced_latent_prefix_then_decode_matches_the_reference():
    """A prompt's latent rows captured from one cache and spliced into a
    slot of another (as the prefix cache does), the rest of the prompt
    chunked from there, then decoding: the same logits as the reference
    over the whole sequence."""
    cfg = tiny()
    params = weights.make(cfg, 6, 0, jax.devices()[0])
    mc = program_cfg(cfg)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 256, 16).tolist()
    _, donor = _chunked(params, mc, [shared + [7, 8, 9]],
                        api.init_cache(mc, 1, 48), [0])
    rows = np.asarray(donor["k"][:, 0, :16])
    prompts = [shared + rng.integers(0, 256, 6).tolist(),
               rng.integers(0, 256, 11).tolist()]
    cache = api.splice_prefix(api.init_cache(mc, 2, 48), 0, rows, None)
    assert int(cache["length"][0]) == 16
    last, cache = _chunked(params, mc, prompts, cache, [16, 0])
    _assert_matches(params, cfg, prompts,
                    _decode(params, mc, prompts, last, cache))


def test_absorbed_attention_equals_the_decompressed_form():
    """The program's attention (queries folded into latent space, one
    cached row per token) against the reference's (the latent decompressed
    into per-head keys and values)."""
    cfg = tiny()
    params = weights.make(cfg, 7, 0, jax.devices()[0])
    layer = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                         params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 64), jnp.float32)
    mc = program_cfg(cfg)
    from repro.models.layers import rms_norm
    got = x + mla.attention_full(
        layer["attn"], rms_norm(x, layer["norm_attn"], mc.norm_eps), mc)
    mask = jnp.asarray(np.tril(np.ones((12, 12), bool)))
    want = MLA._attention(x, layer, reference.Arch.of(cfg), mask, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_yarn_frequencies_and_scale_are_the_published_formula():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4096 positions, beta 32 and
    1, mscale 0.707 on both): the ramp runs over rotary pairs 10..23, the
    softmax scale is 192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2."""
    ds = published()[1]
    dim = 64
    extra = 1.0 / 10000.0 ** (np.arange(0, dim, 2) / dim)
    low = math.floor(dim * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(dim * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    scale = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert scale == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)
    mc = ModelConfig(name=DS, **ds)
    np.testing.assert_allclose(mla.yarn_inv_freq(mc), want, rtol=1e-6)
    assert mla.softmax_scale(mc) == pytest.approx(scale, rel=1e-12)
    assert mla.rope_scale(mc) == 1.0
    a = reference.Arch.of(ds)
    np.testing.assert_allclose(MLA.yarn_inv_freq(a), want, rtol=1e-12)
    assert MLA.softmax_scale(a) == pytest.approx(scale, rel=1e-12)


def _moe_params(cfg, seed):
    params = weights.make(cfg, seed, 0, jax.devices()[0])
    return jax.tree.map(lambda a: a[0].astype(jnp.float32),
                        params["layers"]["moe"])


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of 4 experts each: their routed parts, with the shared
    experts counted once, add up to the reference's whole layer."""
    whole = tiny(experts_first=0, experts_held=0)
    m = _moe_params(whole, 8)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, 64), jnp.float32)
    shared = np.asarray(MLA._swiglu(x, m["shared"], None))
    total = shared
    for first in range(0, 16, 4):
        mc = program_cfg(tiny(experts_first=first, experts_held=4))
        part = dict(m, **{k: m[k][first:first + 4]
                          for k in ("wi_gate", "wi_up", "wo")})
        total = total + np.asarray(moe.held_experts_block(part, x, mc)) \
            - shared
    want = MLA.moe_layer(x, m, reference.Arch.of(whole))
    np.testing.assert_allclose(total, np.asarray(want), atol=2e-5)


def test_a_batch_routed_to_held_experts_alone_drops_none():
    """Sixteen decode tokens whose top 4 are all held experts 4..7: each
    gets all four, as the reference gives them (a capacity of the old
    layer's kind would keep 1.25 x 16 x 4 / 16 = 5 of 16 per expert)."""
    cfg = tiny()
    m = _moe_params(cfg, 9)
    m["router"] = m["router"].at[:, 4:8].add(3.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (16, 1, 64)))
    mc = program_cfg(cfg)
    logits = np.asarray(x[:, 0] @ m["router"])
    assert (np.argsort(-logits, -1)[:, :4] >= 4).all() and \
        (np.argsort(-logits, -1)[:, :4] < 8).all()
    got = np.asarray(moe.held_experts_block(m, x, mc))
    want = np.asarray(MLA.moe_layer(x, m, reference.Arch.of(cfg)))
    np.testing.assert_allclose(got, want, atol=2e-5)
    no_routed = np.asarray(MLA._swiglu(x, m["shared"], None))
    assert (np.abs(got - no_routed).max(-1) > 1e-3).all()


# -- the reader of the new per-layer metric ------------------------------------------


PLANE = "/device:TPU:0"
I = trace_reduce.Interval


def test_the_reader_times_the_models_own_program(monkeypatch, capsys):
    """Two one-token ticks of each model in turn.  The device's clock runs
    0.2 s early against the host's, so each run starts inside the tick
    before its own; the vote by overlap still gives each program to its
    model, and the reader times DeepSeek's program alone."""
    tr = spans.Tracer()
    monkeypatch.setattr(spans, "TRACER", tr)
    for model in (DS, "rwkv6-1.6b"):
        spans.engine_span_names(tr, model)
    tr.record("pool.step", -0.5, 9.5)
    for t in (1.0, 3.0):
        tr.record(f"engine.tick:{DS}", t, t + 1.0)
        tr.record("engine.tick:rwkv6-1.6b", t + 1.0, t + 2.0)
    off, skew = 50.0, -0.2
    ds_prog, rwkv_prog = "jit_greedy_step(1)", "jit_greedy_step(2)"
    runs = [I(name, a + off + skew, b + off + skew) for name, a, b in (
        (ds_prog, 1.1, 1.8), (rwkv_prog, 2.1, 2.7),
        (ds_prog, 3.1, 3.8), (rwkv_prog, 4.1, 4.7),
        ("jit_greedy_chunk_step(3)", 4.8, 4.9))]
    ds = published()[1]
    cell = types.SimpleNamespace(models={DS: ds, "rwkv6-1.6b": {}})
    calls = [harness.Call(DS, "decode", 1.0, 2.0, [(10, 1), (20, 1)]),
             harness.Call("rwkv6-1.6b", "decode", 2.0, 3.0, [(5, 1)]),
             harness.Call(DS, "decode", 3.0, 4.0, [(11, 1)]),
             harness.Call("rwkv6-1.6b", "decode", 4.0, 5.0, [(6, 1)])]
    peaks = types.SimpleNamespace(flops_bf16=197e12, hbm_bw=819e9)
    run = types.SimpleNamespace(
        trace={"lo": off, "hi": off + 10.0, "offset": off, "window_s": 10.0,
               "devices": {PLANE: {"runs": runs}}},
        peaks=peaks, planes=frozenset({PLANE}), cell=cell, calls=calls,
        flops=flops)
    read = harness.layer_reader("greedy_step_roofline.mla_moe")
    least = sum(flops.least_time(*flops.step_cost(ds, p), 197e12, 819e9)[0]
                for p in ([10, 20], [11]))
    assert read(run) == pytest.approx(100 * least / 1.4)
    err = capsys.readouterr().err
    assert f"'{ds_prog}': ('{DS}', 2), '{rwkv_prog}': ('rwkv6-1.6b', 2)" \
        in err
    assert "2 ticks, 2 device runs, bound by {'compute': 0, " \
        "'bandwidth': 2}" in err
    # a ring that no longer holds the whole slice, or no trace: nothing
    monkeypatch.setattr(spans, "TRACER", spans.Tracer(capacity=2))
    for model in (DS, "rwkv6-1.6b"):
        spans.engine_span_names(spans.TRACER, model)
    for _ in range(3):
        spans.TRACER.record(f"engine.tick:{DS}", 1.0, 2.0)
    assert read(run) is None
    run.trace = None
    assert read(run) is None


# -- a tiny run of the pool through the harness ---------------------------------------


TINY_MIX = {
    "lists": {"topic": ["entropy", "the silk road", "market liquidity"],
              "noun": ["plants", "archives", "reactors"]},
    "tasks": [
        {"task": "QA", "share": 1, "max_new_tokens": 24,
         "template": "Answer the question.\nWhich statement about {topic} "
                     "involves {noun}?\nAnswer:",
         "slots": {"topic": {"from": "topic"}, "noun": {"from": "noun"}}},
        {"task": "SUMMARIZATION", "share": 1, "max_new_tokens": 32,
         "template": "Summarize the article.\nArticle: the committee on "
                     "{topic} studied {noun}.\nSummary:",
         "slots": {"topic": {"from": "topic"}, "noun": {"from": "noun"}}}]}
RWKV_TINY = dict(n_layers=2, d_model=128, n_heads=2, n_kv_heads=2,
                 head_dim=64, d_ff=256, vocab_size=512)
DS_TINY = dict(TINY, d_model=128, d_ff=256, vocab_size=512)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the new pool at a tiny size as a cell of its own."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(HERE / "traffic", root / "benchmarks/chip/traffic")
    (root / "benchmarks/chip/configs").mkdir()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = published()[0]
    cfg["name"] = "tiny-ds"
    cfg["serving"].update(slots=4, max_len=128)
    cfg["router"]["featurize"] = "host"
    cfg["check"].update(requests_per_model=16, batch=8)
    for m in cfg["models"]:
        m["config"].update(DS_TINY if m["name"] == DS else RWKV_TINY)
        cfg["check"]["limits"][m["name"]] = {"logit_gap": 0.1,
                                             "mean_gap": 0.003}
    (root / "benchmarks/chip/configs/tiny-ds.json").write_text(
        json.dumps(cfg))
    (root / "benchmarks/chip/traffic/tiny-mix.json").write_text(
        json.dumps(TINY_MIX))
    (root / "benchmarks/chip/traffic/tiny-short.json").write_text(
        json.dumps({"mix": "tiny-mix", "arrivals": "poisson",
                    "rate_qps": 8.0, "stream_seed": 0, "warm_s": 0.5,
                    "drain_limit_s": 60}))
    bench["configs"].append({"name": "tiny-ds", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-ds.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.ds", "config": "tiny-ds",
                               "traffic": "tiny-short", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_tiny_run_is_correct_and_its_fp8_control_is_not(root):
    """Served through the program's pool with latent prefix splices: the
    widest gap read 0.012 against the fp8 control's 0.31-0.80."""
    cell = harness.load_cell("tiny.ds", root)
    res = harness.run(cell, 2 ** 31 + 5, 2.0, False, jax.devices()[:1],
                      time.monotonic(), controls=("fp8",))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"][f"{DS}.logit_gap"]["value"] is not None
    assert not res["controls"]["fp8"]["correct"]
