"""Operations and bytes of the served models' work, from their sizes.

Written from the two architectures' equations, at the configuration's
published sizes; nothing here is read from the program.  Conventions:

* An operation is a multiply or an add: a matmul of (m, k) by (k, n) is
  2·m·k·n.  Elementwise work (norms, RoPE, softmax, activations, decay
  exponentials) is left out; it is under 1% of either model's total.
* One token of model work at position ``pos`` (``pos`` tokens already in
  its cache) is every weight matmul once, attention over ``pos + 1`` keys
  (within the window) for the dense model, the WKV state update for rwkv,
  and the LM head.  The embedding lookup is a gather and costs nothing.
* Bytes are what the work needs to move at least: every weight read once
  per call (bf16, rwkv's decay and bonus vectors float32), each live
  slot's cache or state read once and what the step adds written once.
  Empty slots need nothing, so a call's least time counts live slots only.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

BF16, F32 = 2, 4
RWKV_HEAD_DIM = 64
RWKV_LORA_RANK = 32


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that a token multiplies through."""
    d, f = cfg["d_model"], cfg["d_ff"]
    if cfg["layout"] == "dense":
        hd = cfg["head_dim"]
        q = d * cfg["n_heads"] * hd
        kv = 2 * d * cfg["n_kv_heads"] * hd
        o = cfg["n_heads"] * hd * d
        return q + kv + o + 3 * d * f
    if cfg["layout"] == "rwkv":
        # r, k, v, g, output and the channel mix's receptance (d x d each),
        # the decay LoRA, and the channel mix's key and value
        return 6 * d * d + 2 * d * RWKV_LORA_RANK + 2 * d * f
    raise ValueError(f"no operation count for layout {cfg['layout']!r}")


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight a call reads: layers, final norm, LM head.
    (The embedding table is gathered a row per token, not read whole.)"""
    d, L = cfg["d_model"], cfg["n_layers"]
    head = d * cfg["vocab_size"] * BF16 + d * BF16
    if cfg["layout"] == "dense":
        per_layer = layer_matmul_params(cfg) * BF16 + 2 * d * BF16
    else:
        # mu x7, ln_x, ln1, ln2 in bf16; decay_base and bonus_u in float32
        per_layer = (layer_matmul_params(cfg) * BF16 + 10 * d * BF16
                     + 2 * d * F32)
    return L * per_layer + head


def window(cfg: dict) -> int:
    """Keys a dense token attends to at most: the sliding window, or every
    position where attention is full."""
    if cfg.get("attn_pattern", "full") == "full":
        return cfg["max_seq_len"]
    return cfg["window"]


def token_flops(cfg: dict, pos: int) -> float:
    """Operations of one token at cache position ``pos``."""
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    per_layer = 2 * layer_matmul_params(cfg)
    if cfg["layout"] == "dense":
        keys = min(pos + 1, window(cfg))
        # scores q.k and the weighted sum p.v: 2 flops per multiply-add each
        per_layer += 4 * cfg["n_heads"] * cfg["head_dim"] * keys
    else:
        # per head: k (x) v, S + u*kv, r.(.), S*w + kv over a K x V state
        h = d // RWKV_HEAD_DIM
        per_layer += 7 * h * RWKV_HEAD_DIM * RWKV_HEAD_DIM
    return float(L * per_layer + 2 * d * V)


def state_bytes(cfg: dict, pos: int) -> float:
    """Cache or state bytes one slot's token reads and writes at ``pos``."""
    L, d = cfg["n_layers"], cfg["d_model"]
    if cfg["layout"] == "dense":
        per_pos = 2 * cfg["n_kv_heads"] * cfg["head_dim"] * BF16   # k and v
        keys = min(pos, window(cfg) - 1)
        return float(L * per_pos * (keys + 1))   # read the cache, write one
    h = d // RWKV_HEAD_DIM
    wkv = h * RWKV_HEAD_DIM * RWKV_HEAD_DIM * F32
    shifts = 2 * d * F32
    return float(L * 2 * (wkv + shifts))          # read and write the state


def step_cost(cfg: dict, positions: Sequence[int]) -> Tuple[float, float]:
    """(operations, bytes) of one one-token tick over live slots whose
    tokens sit at ``positions``."""
    if not positions:
        return 0.0, 0.0
    flops = sum(token_flops(cfg, p) for p in positions)
    nbytes = weight_bytes(cfg) + sum(state_bytes(cfg, p) for p in positions)
    return flops, float(nbytes)


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> Tuple[float, str]:
    """(seconds, bound): the roofline's least time and which side binds."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")


def param_count(cfg: dict) -> Dict[str, int]:
    """Parameter counts, to check the sizes against the materialized tree."""
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    vectors = 2 * d if cfg["layout"] == "dense" else 12 * d
    return {"layers": L * (layer_matmul_params(cfg) + vectors),
            "embed_and_head": 2 * d * V, "final_norm": d}
