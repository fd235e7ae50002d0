"""The harness end to end at a tiny size on the CPU.

It finds a new cell by name from data files alone; refuses to run without
a TPU; and, with its look for a chip skipped, drives a whole run of a tiny
two-model pool whose ``correct`` comes out true for the program as it is
and false with the served path broken underneath: a token altered where
it is produced, a step that returns its state unchanged, half of the
slots left out, and a routing decision altered where it is made.  (The
fourth fault a cell can have, the exchange between chips left out, does
not apply: the pool runs on one chip.)  The control in the next precision
down, fp8 for the models and ``high`` for the router, is not correct under
the cell's limits at the same size.
"""
import collections
import functools
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402

TINY_MODELS = {
    "h2o-danube-3-4b": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                            head_dim=32, d_ff=256, vocab_size=512),
    "rwkv6-1.6b": dict(n_layers=2, d_model=128, n_heads=2, n_kv_heads=2,
                       head_dim=64, d_ff=256, vocab_size=512),
}
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
TINY_LOAD = {"mix": "tiny-mix", "arrivals": "poisson",
             "rate_qps": 8.0, "stream_seed": 0, "warm_s": 0.5,
             "drain_limit_s": 60}
# set from this file's sound runs on seeds 1-4 (widest gap 0.005-0.023,
# mean 0.00006-0.00056) and fp8 control (widest 0.33-0.53, mean
# 0.014-0.045); the three faults read 2.1-7.0 widest, 0.05-3.5 mean
TINY_LIMITS = {"logit_gap": 0.1, "mean_gap": 0.003}
# the router's score gap read 4.9e-8-7.9e-8 on seeds 1-3, 5 and 6 and its
# ``high`` control 6.3e-6-8.3e-6
ROUTER_LIMITS = {"arm_mismatch": 0, "score_gap": 1e-6}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding the real benchmark plus one new cell, added as
    entries and data files only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(HERE / "configs", root / "benchmarks/chip/configs")
    shutil.copytree(HERE / "traffic", root / "benchmarks/chip/traffic")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((HERE / "configs/pool2-danube4b-rwkv1.6b.json")
                     .read_text())
    cfg["name"] = "tiny-pool"
    cfg["serving"].update(slots=4, max_len=128)
    cfg["router"]["featurize"] = "host"
    cfg["check"].update(requests_per_model=16, batch=8)
    for m in cfg["models"]:
        m["config"].update(TINY_MODELS[m["name"]])
        cfg["check"]["limits"][m["name"]] = dict(TINY_LIMITS)
    cfg["check"]["limits"]["router"] = dict(ROUTER_LIMITS)
    (root / "benchmarks/chip/configs/tiny-pool.json").write_text(
        json.dumps(cfg))
    (root / "benchmarks/chip/traffic/tiny-mix.json").write_text(
        json.dumps(TINY_MIX))
    (root / "benchmarks/chip/traffic/tiny-short.json").write_text(
        json.dumps(TINY_LOAD))
    bench["configs"].append({"name": "tiny-pool", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-pool.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.short", "config": "tiny-pool",
                               "traffic": "tiny-short", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"].append("tiny.short")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_new_cell_is_found_by_name_from_data_files(root):
    cell = harness.load_cell("tiny.short", root)
    assert cell.config["name"] == "tiny-pool" and cell.chips == 1
    assert [t["task"] for t in cell.load["mix"]["tasks"]] == \
        ["QA", "SUMMARIZATION"]
    assert {m["name"] for m in cell.end_to_end} == \
        {"setup_s", "tbt_p95_ms", "output_tokens_per_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.layer_reader(m["name"]))
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell", root)


# A layout of its own, written as a new architecture's PR would write it:
# residual ReLU layers and a head tied to the embedding.
TOY_ARCH = '''
import dataclasses

import jax
import jax.numpy as jnp

import reference
from weights import _normal


@dataclasses.dataclass(frozen=True)
class Arch(reference.Arch):
    norm_eps: float


def arch(cfg):
    return Arch(layout=cfg["layout"], norm_eps=1e-6)


def leaf_specs(cfg):
    L, d, V = cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]
    return [("tok/embed", (V, d), "bfloat16", "normal", 1.0, 0.0),
            ("norm_f", (d,), "bfloat16", "ones", 0.0, 0.0),
            _normal("layers/w", (L, d, d), d)]


def hidden(params, tokens, a, quant=None):
    x = params["tok"]["embed"][tokens].astype(jnp.float32)

    def layer(x, w):
        return x + jax.nn.relu(reference._mm(x, w.astype(jnp.float32),
                                             quant)), None

    x, _ = jax.lax.scan(layer, x, params["layers"]["w"])
    return reference._rms(x, params["norm_f"].astype(jnp.float32),
                          a.norm_eps)


def head(params):
    return params["tok"]["embed"].T


def layer_matmul_params(cfg):
    return cfg["d_model"] ** 2


def weight_bytes(cfg):
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    return 2 * (L * d * d + d * V + d)


def token_flops(cfg, pos):
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    return float(2 * L * layer_matmul_params(cfg) + 2 * d * V)


def state_bytes(cfg, pos):
    return 0.0


def param_count(cfg):
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    return {"layers": L * d * d, "embed_and_head": d * V, "final_norm": d}
'''
TOY = {"layout": "toy", "n_layers": 2, "d_model": 16, "vocab_size": 64}


def test_a_new_architecture_is_found_by_name_from_its_module(root,
                                                             monkeypatch):
    """A toy layout's module, written into the checkout's ``arch/``, is
    what the weights, the counts and the comparison use."""
    import numpy as np

    import check
    import flops
    import layouts
    import reference
    import weights

    arch_dir = root / "benchmarks/chip/arch"
    shutil.copytree(HERE / "arch", arch_dir, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (arch_dir / "toy.py").write_text(TOY_ARCH)
    monkeypatch.setattr(layouts, "ARCH_DIR", arch_dir)

    params = weights.make(TOY, 3, 0, jax.devices()[0])
    shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    assert shapes == {"tok": {"embed": ((64, 16), "bfloat16")},
                      "norm_f": ((16,), "bfloat16"),
                      "layers": {"w": ((2, 16, 16), "bfloat16")}}
    assert sum(flops.param_count(TOY).values()) == sum(
        a.size for a in jax.tree.leaves(params))
    assert flops.step_cost(TOY, [0, 9]) == (
        2 * (2 * 2 * 256 + 2 * 16 * 64.0), 2 * (2 * 256 + 16 * 64 + 16.0))

    # greedy tokens of the toy's own reference, one position at a time
    a = reference.Arch.of(TOY)
    head = np.asarray(params["tok"]["embed"], np.float32).T
    prompt, out = [5, 17, 40, 2], []
    for _ in range(4):
        hid = layouts.module("toy").hidden(
            params, jnp.asarray([prompt + out]), a)
        out.append(int(np.argmax(np.asarray(hid)[0, -1] @ head)))
    Row = collections.namedtuple("Row", "model prompt generated")
    kwargs = dict(weights={"toy": params}, models={"toy": TOY}, length=16,
                  n_out=4, batch=2, controls=("fp8",))
    sound = check.widest_gaps([Row("toy", prompt, out)], **kwargs)["toy"]
    assert sound["tokens"] == 4 and sound["gap"] < 1e-3, sound
    assert "control_gap.fp8" in sound
    wrong = [out[0], (out[1] + 1) % 64] + out[2:]
    broken = check.widest_gaps([Row("toy", prompt, wrong)], **kwargs)["toy"]
    assert broken["gap"] > 0.1, broken


@pytest.mark.parametrize("entry", ["weights.leaf_specs", "flops.token_flops",
                                   "reference.Arch.of"])
def test_a_layout_with_no_module_raises_naming_the_file(entry):
    import flops
    import reference
    import weights
    calls = {"weights.leaf_specs": lambda c: weights.leaf_specs(c),
             "flops.token_flops": lambda c: flops.token_flops(c, 0),
             "reference.Arch.of": lambda c: reference.Arch.of(c)}
    with pytest.raises(ValueError, match=r"arch/no_such_layout\.py"):
        calls[entry]({"layout": "no_such_layout", "d_model": 8})


def _run_py(cwd, env):
    return subprocess.run(
        [sys.executable, str(cwd / "benchmarks/chip/run.py"), "--workload",
         "pool2.paper-mix.steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=240)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run_py(ROOT, env)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "no TPU" in out.stderr
    assert "{" not in out.stdout


def test_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = _run_py(tmp_path, env)
    assert out.returncode != 0
    assert "{" not in out.stdout


# -- faults planted in the served path ------------------------------------


def _token_altered(orig):
    def step(params, cache, tokens, cfg):
        tok, cache = orig(params, cache, tokens, cfg=cfg)
        return (tok + 1) % cfg.vocab_size, cache
    return step


def _state_unchanged(orig):
    from repro.models import api

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def step(params, cache, tokens, cfg):
        logits, _ = api.serve_step(params, tokens, cache, cfg)
        return jnp.argmax(logits[:, 0], -1).astype(jnp.int32), cache
    return step


def _half_left_out(orig):
    def step(params, cache, tokens, cfg):
        tok, cache = orig(params, cache, tokens, cfg=cfg)
        odd = jnp.arange(tok.shape[0]) % 2 == 1
        return jnp.where(odd, 0, tok), cache
    return step


FAULTS = {"token_altered": _token_altered,
          "state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out}


def _tiny_run(root, seed, controls=()):
    cell = harness.load_cell("tiny.short", root)
    return harness.run(cell, seed, 2.0, False, jax.devices()[:1],
                       time.monotonic(), controls=controls)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sound_runs_are_correct_and_the_fp8_control_is_not(root, seed):
    res = _tiny_run(root, seed, controls=("fp8",))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"setup_s", "tbt_p95_ms", "output_tokens_per_s"} <= \
        set(res["metrics"])
    assert res["checks"]["router.arm_mismatch"]["value"] == 0
    ctl = res["controls"]["fp8"]
    assert not ctl["correct"], ctl
    assert set(ctl["checks"]) == set(res["checks"])
    score = ctl["checks"]["router.score_gap"]
    assert score["value"] > score["limit"], score


def test_the_router_state_copies_compile_before_the_window(root):
    """The correctness capture copies the router's state at each decision
    of the window; those copies compile in set-up, not in the window."""
    import mix
    cell = harness.load_cell("tiny.short", root)
    _, dep, _ = harness.build(cell, 1, mix.generate(cell.load, 2.0),
                              jax.devices()[0])
    harness.instrument(dep, [], harness.RouteCapture())
    before = dict(harness.COMPILES)
    router = dep.server.router
    harness._router_state(router, router._device_featurize_active())
    assert harness.COMPILES == before


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_served_path_is_not_correct(root, fault, monkeypatch):
    from repro.serving import engine
    monkeypatch.setattr(engine, "greedy_step",
                        FAULTS[fault](engine.greedy_step))
    res = _tiny_run(root, 1)
    assert not res["correct"], res["checks"]


def test_an_altered_routing_decision_is_not_correct(root, monkeypatch):
    from repro.core import bandits
    orig = bandits.linucb_scores_kernel

    def inverted(a_inv, theta, x, alpha):
        return -orig(a_inv, theta, x, alpha)
    monkeypatch.setattr(bandits, "linucb_scores_kernel", inverted)
    res = _tiny_run(root, 1)
    assert not res["correct"]
    assert res["checks"]["router.arm_mismatch"]["value"] > 0, res["checks"]
