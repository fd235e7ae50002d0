"""Each layout's weights, plain reference and counts come from its
``arch/<layout>.py``, and match what they were before the split, bit for
bit.

The expected values below were recorded from the benchmark as it was
before the layouts moved into ``arch/``: the leaf specs of both published
models, and the reference's gaps, and its fp8 and int8 controls' gaps, at
a tiny size on the CPU.  ``test_chip_flops.py`` holds the operation and
byte counts.
"""
import json
import pathlib
import re
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import jax  # noqa: E402

import reference  # noqa: E402
import weights  # noqa: E402

DANUBE, RWKV = "h2o-danube-3-4b", "rwkv6-1.6b"


def models():
    cfg = json.loads((HERE / "configs" / "pool2-danube4b-rwkv1.6b.json")
                     .read_text())
    return {m["name"]: m["config"] for m in cfg["models"]}


SPECS = {
    DANUBE: [
        ("tok/embed", (32000, 3840), "bfloat16", "normal", 0.02, 0.0),
        ("tok/unembed", (3840, 32000), "bfloat16", "normal", 0.01613743060919757, 0.0),
        ("norm_f", (3840,), "bfloat16", "ones", 0.0, 0.0),
        ("layers/norm_attn", (24, 3840), "bfloat16", "ones", 0.0, 0.0),
        ("layers/attn/wq", (24, 3840, 32, 120), "bfloat16", "normal", 0.01613743060919757, 0.0),
        ("layers/attn/wk", (24, 3840, 8, 120), "bfloat16", "normal", 0.01613743060919757, 0.0),
        ("layers/attn/wv", (24, 3840, 8, 120), "bfloat16", "normal", 0.01613743060919757, 0.0),
        ("layers/attn/wo", (24, 32, 120, 3840), "bfloat16", "normal", 0.01613743060919757, 0.0),
        ("layers/norm_mlp", (24, 3840), "bfloat16", "ones", 0.0, 0.0),
        ("layers/mlp/wi_gate", (24, 3840, 10240), "bfloat16", "normal", 0.01613743060919757, 0.0),
        ("layers/mlp/wi_up", (24, 3840, 10240), "bfloat16", "normal", 0.01613743060919757, 0.0),
        ("layers/mlp/wo", (24, 10240, 3840), "bfloat16", "normal", 0.009882117688026186, 0.0),
    ],
    RWKV: [
        ("tok/embed", (65536, 2048), "bfloat16", "normal", 0.02, 0.0),
        ("tok/unembed", (2048, 65536), "bfloat16", "normal", 0.022097086912079608, 0.0),
        ("norm_f", (2048,), "bfloat16", "ones", 0.0, 0.0),
        ("layers/ln1", (24, 2048), "bfloat16", "ones", 0.0, 0.0),
        ("layers/ln2", (24, 2048), "bfloat16", "ones", 0.0, 0.0),
        ("layers/rwkv/mu_r", (24, 2048), "bfloat16", "uniform", 0.0, 1.0),
        ("layers/rwkv/mu_k", (24, 2048), "bfloat16", "uniform", 0.0, 1.0),
        ("layers/rwkv/mu_v", (24, 2048), "bfloat16", "uniform", 0.0, 1.0),
        ("layers/rwkv/mu_w", (24, 2048), "bfloat16", "uniform", 0.0, 1.0),
        ("layers/rwkv/mu_g", (24, 2048), "bfloat16", "uniform", 0.0, 1.0),
        ("layers/rwkv/mu_ck", (24, 2048), "bfloat16", "uniform", 0.0, 1.0),
        ("layers/rwkv/mu_cr", (24, 2048), "bfloat16", "uniform", 0.0, 1.0),
        ("layers/rwkv/wr", (24, 2048, 2048), "bfloat16", "normal", 0.022097086912079608, 0.0),
        ("layers/rwkv/wk", (24, 2048, 2048), "bfloat16", "normal", 0.022097086912079608, 0.0),
        ("layers/rwkv/wv", (24, 2048, 2048), "bfloat16", "normal", 0.022097086912079608, 0.0),
        ("layers/rwkv/wg", (24, 2048, 2048), "bfloat16", "normal", 0.022097086912079608, 0.0),
        ("layers/rwkv/wo_tm", (24, 2048, 2048), "bfloat16", "normal", 0.022097086912079608, 0.0),
        ("layers/rwkv/cr", (24, 2048, 2048), "bfloat16", "normal", 0.022097086912079608, 0.0),
        ("layers/rwkv/decay_base", (24, 2048), "float32", "uniform", -8.0, -4.0),
        ("layers/rwkv/lora_a_decay", (24, 2048, 32), "bfloat16", "normal", 0.022097086912079608, 0.0),
        ("layers/rwkv/lora_b_decay", (24, 32, 2048), "bfloat16", "normal", 0.17677669529663687, 0.0),
        ("layers/rwkv/bonus_u", (24, 2048), "float32", "normal", 0.1, 0.0),
        ("layers/rwkv/ln_x", (24, 2048), "bfloat16", "ones", 0.0, 0.0),
        ("layers/rwkv/ck", (24, 2048, 7168), "bfloat16", "normal", 0.022097086912079608, 0.0),
        ("layers/rwkv/cv", (24, 7168, 2048), "bfloat16", "normal", 0.01181138978153835, 0.0),
    ],
}


@pytest.mark.parametrize("name", [DANUBE, RWKV])
def test_leaf_specs_of_the_published_configs_are_unchanged(name):
    """The same specs in the same order: each leaf's draw folds in its
    index, so the order is part of the weights."""
    assert weights.leaf_specs(models()[name]) == SPECS[name]


TINY = {DANUBE: dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                     head_dim=32, d_ff=256, vocab_size=512),
        RWKV: dict(n_layers=2, d_model=128, n_heads=2, n_kv_heads=2,
                   head_dim=64, d_ff=256, vocab_size=512)}
# (gap, gap_sum, control_gap, control_gap_sum) of rows 0 and 1; row 2 is
# padding and reads 0.  Served tokens are random, so ``gap`` is wide.
GAPS = {
    (DANUBE, None): ([5.395778179168701, 4.510341644287109],
                     [18.837505340576172, 8.683877944946289], None, None),
    (DANUBE, "fp8"): ([5.395778179168701, 4.510341644287109],
                      [18.837505340576172, 8.683877944946289],
                      [0.01851034164428711, 0.07052803039550781],
                      [0.01851034164428711, 0.07052803039550781]),
    (DANUBE, "int8"): ([5.395778179168701, 4.510341644287109],
                       [18.837505340576172, 8.683877944946289],
                       [0.01851034164428711, 0.0],
                       [0.01851034164428711, 0.0]),
    (RWKV, None): ([5.753144264221191, 4.32746696472168],
                   [20.344112396240234, 10.475404739379883], None, None),
    (RWKV, "fp8"): ([5.753144264221191, 4.32746696472168],
                    [20.344112396240234, 10.475404739379883],
                    [0.7587251663208008, 0.05790972709655762],
                    [0.7587251663208008, 0.05790972709655762]),
    (RWKV, "int8"): ([5.753144264221191, 4.32746696472168],
                     [20.344112396240234, 10.475404739379883],
                     [0.0, 0.05790972709655762],
                     [0.0, 0.05790972709655762]),
}


@pytest.mark.parametrize("name,control", sorted(GAPS, key=str))
def test_the_reference_and_its_controls_read_as_before(name, control):
    cfg = dict(models()[name], **TINY[name])
    params = weights.make(cfg, 5, 1, jax.devices()[0])
    rng = np.random.default_rng(7)
    rows = [(rng.integers(0, 512, 9).tolist(), rng.integers(0, 512, 5).tolist()),
            (rng.integers(0, 512, 14).tolist(), rng.integers(0, 512, 3).tolist())]
    got = reference.served_gaps(params, *reference.pack(rows, 24, 6, 3),
                                a=reference.Arch.of(cfg), control=control)
    keys = ("gap", "gap_sum", "control_gap", "control_gap_sum")
    assert set(got) == {k for k, v in zip(keys, GAPS[name, control])
                        if v is not None}
    for key, want in zip(keys, GAPS[name, control]):
        if want is not None:
            assert np.asarray(got[key]).tolist() == want + [0.0], key


SHARED = ("weights.py", "reference.py", "flops.py", "check.py", "harness.py")


@pytest.mark.parametrize("path", SHARED)
def test_shared_files_name_no_layout(path):
    """Only ``arch/<layout>.py`` knows a layout: the shared files hold no
    layout as a string or a constant."""
    source = (HERE / path).read_text()
    for layout in sorted(p.stem for p in (HERE / "arch").glob("*.py")):
        assert not re.search(rf"[\"']{layout}[\"']|{layout.upper()}_",
                             source), layout
