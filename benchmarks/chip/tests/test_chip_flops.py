"""``flops.py`` against counts worked by hand at the published widths."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import flops  # noqa: E402


def models():
    cfg = json.loads((HERE / "configs" / "pool2-danube4b-rwkv1.6b.json")
                     .read_text())
    return {m["name"]: m["config"] for m in cfg["models"]}


DANUBE, RWKV = "h2o-danube-3-4b", "rwkv6-1.6b"


def test_danube_counts():
    c = models()[DANUBE]
    # q 3840*32*120 + k,v 2*3840*8*120 + o 32*120*3840 + SwiGLU 3*3840*10240
    assert flops.layer_matmul_params(c) == (14_745_600 + 7_372_800
                                            + 14_745_600 + 117_964_800)
    # every parameter, as the materialized bf16 tree holds them
    assert sum(flops.param_count(c).values()) == 3_961_839_360
    # one token at position 0: 2 x matmul weights + attention over 1 key
    # (4 * 32 heads * 120) per layer, and the 3840 x 32000 LM head
    assert flops.token_flops(c, 0) == 24 * (2 * 154_828_800 + 15_360) \
        + 2 * 3840 * 32000
    # attention grows by 4*H*hd per layer per key, up to the window
    assert flops.token_flops(c, 100) - flops.token_flops(c, 0) == \
        24 * 4 * 32 * 120 * 100
    # k and v of 8 heads x 120 in bf16, 24 layers, read and one written
    assert flops.state_bytes(c, 0) == 24 * 3840
    assert flops.state_bytes(c, 100) == 24 * 3840 * 101
    # full attention: no window caps the keys; a sliding one does
    assert flops.window(c) == 8192
    assert flops.window(dict(c, attn_pattern="swa", window=64)) == 64
    # layers and norms in bf16, LM head and final norm; no embedding table
    assert flops.weight_bytes(c) == 24 * (2 * 154_828_800 + 2 * 3840 * 2) \
        + 3840 * 32000 * 2 + 3840 * 2


def test_rwkv_counts():
    c = models()[RWKV]
    # 6 d x d, the 2048 x 32 decay LoRA pair, the 2048 x 7168 channel mix pair
    assert flops.layer_matmul_params(c) == 25_165_824 + 131_072 + 29_360_128
    assert sum(flops.param_count(c).values()) == 1_580_795_904
    # + WKV: 7 flops per state entry, 32 heads of 64 x 64
    assert flops.token_flops(c, 0) == 24 * (2 * 54_657_024 + 917_504) \
        + 2 * 2048 * 65536
    assert flops.token_flops(c, 500) == flops.token_flops(c, 0)
    # float32 state: 32 x 64 x 64 WKV + two 2048 shifts, read and written
    assert flops.state_bytes(c, 7) == 24 * 2 * (524_288 + 16_384)
    assert flops.weight_bytes(c) == 24 * (2 * 54_657_024 + 10 * 2048 * 2
                                          + 2 * 2048 * 4) \
        + 2048 * 65536 * 2 + 2048 * 2


def test_step_cost_and_its_bound():
    c = models()[DANUBE]
    positions = [500] * 16
    f, b = flops.step_cost(c, positions)
    assert f == 16 * flops.token_flops(c, 500)
    assert b == flops.weight_bytes(c) + 16 * flops.state_bytes(c, 500)
    t, bound = flops.least_time(f, b, 197e12, 819e9)
    assert bound == "bandwidth" and t == pytest.approx(b / 819e9)
    t, bound = flops.least_time(1e15, 1.0, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)
    assert flops.step_cost(c, []) == (0.0, 0.0)


def test_peaks_of_the_v5e_and_no_default():
    import peaks
    p = peaks.peaks("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bw, p.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
