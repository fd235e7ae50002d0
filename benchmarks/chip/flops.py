"""Operations and bytes of the served models' work, from their sizes.

Each architecture's counts are in its ``arch/<layout>.py``
(``layouts.py``), written from its equations at the configuration's
published sizes; a new architecture adds them there and nothing here.
Nothing is read from the program.  This file holds the dispatch, the
roofline of a step and the conventions every count keeps:

* An operation is a multiply or an add: a matmul of (m, k) by (k, n) is
  2·m·k·n.  Elementwise work (norms, RoPE, softmax, activations, decay
  exponentials) is left out; it is under 1% of a model's total.
* One token of model work at position ``pos`` (``pos`` tokens already in
  its cache) is every weight matmul once, the work that reads the cache or
  state (attention over ``pos + 1`` keys within the window, a recurrent
  state's update), and the LM head.  The embedding lookup is a gather and
  costs nothing.
* Bytes are what the work needs to move at least: every weight read once
  per call in the type it is served in, each live slot's cache or state
  read once and what the step adds written once.  Empty slots need
  nothing, so a call's least time counts live slots only.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import layouts

BF16, F32 = 2, 4


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that a token multiplies through."""
    return layouts.of(cfg).layer_matmul_params(cfg)


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight a call reads: layers, final norm, LM head.
    (The embedding table is gathered a row per token, not read whole.)"""
    return layouts.of(cfg).weight_bytes(cfg)


def window(cfg: dict) -> int:
    """Keys an attention token attends to at most: the sliding window, or
    every position where attention is full."""
    if cfg.get("attn_pattern", "full") == "full":
        return cfg["max_seq_len"]
    return cfg["window"]


def token_flops(cfg: dict, pos: int) -> float:
    """Operations of one token at cache position ``pos``."""
    return layouts.of(cfg).token_flops(cfg, pos)


def state_bytes(cfg: dict, pos: int) -> float:
    """Cache or state bytes one slot's token reads and writes at ``pos``."""
    return layouts.of(cfg).state_bytes(cfg, pos)


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
    return layouts.of(cfg).param_count(cfg)
