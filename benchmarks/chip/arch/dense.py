"""The dense decoder (h2o-danube-3): weights, plain reference and counts.

Pre-norm blocks, RMSNorm (eps from the config), rotary embedding on the
two halves of each head, grouped-query causal attention (inside the
sliding window where the configuration has one), SwiGLU MLP, untied LM
head.  The reference runs a whole sequence at once.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import flops
import reference
from flops import BF16
from reference import HI, _f32, _mm, _rms, _rope
from weights import Spec, _normal


# -- weights ---------------------------------------------------------------------


def leaf_specs(cfg: dict) -> List[Spec]:
    L, d, f, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    H, Hk, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return [
        ("tok/embed", (V, d), "bfloat16", "normal", 0.02, 0.0),
        _normal("tok/unembed", (d, V), d),
        ("norm_f", (d,), "bfloat16", "ones", 0.0, 0.0),
        ("layers/norm_attn", (L, d), "bfloat16", "ones", 0.0, 0.0),
        _normal("layers/attn/wq", (L, d, H, hd), d),
        _normal("layers/attn/wk", (L, d, Hk, hd), d),
        _normal("layers/attn/wv", (L, d, Hk, hd), d),
        _normal("layers/attn/wo", (L, H, hd, d), H * hd),
        ("layers/norm_mlp", (L, d), "bfloat16", "ones", 0.0, 0.0),
        _normal("layers/mlp/wi_gate", (L, d, f), d),
        _normal("layers/mlp/wi_up", (L, d, f), d),
        _normal("layers/mlp/wo", (L, f, d), f),
    ]


# -- reference -------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Arch(reference.Arch):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int
    rope_theta: float
    norm_eps: float


def arch(cfg: dict) -> Arch:
    return Arch(layout=cfg["layout"], d_model=cfg["d_model"],
                n_heads=cfg["n_heads"], n_kv_heads=cfg["n_kv_heads"],
                head_dim=cfg["head_dim"], window=flops.window(cfg),
                rope_theta=cfg["rope_theta"], norm_eps=cfg["norm_eps"])


def hidden(params, tokens, a: Arch, quant=None) -> jax.Array:
    """Final-normed hidden states (B, T, d)."""
    b, t = tokens.shape
    H, Hk, hd, d = a.n_heads, a.n_kv_heads, a.head_dim, a.d_model
    x = params["tok"]["embed"][tokens].astype(jnp.float32)
    qpos = np.arange(t)[:, None]
    kpos = np.arange(t)[None, :]
    mask = jnp.asarray((kpos <= qpos) & (kpos > qpos - a.window))

    def layer(x, lp):
        lp = _f32(lp)
        h = _rms(x, lp["norm_attn"], a.norm_eps)
        q = _mm(h, lp["attn"]["wq"].reshape(d, H * hd), quant).reshape(b, t, H, hd)
        k = _mm(h, lp["attn"]["wk"].reshape(d, Hk * hd), quant).reshape(b, t, Hk, hd)
        v = _mm(h, lp["attn"]["wv"].reshape(d, Hk * hd), quant).reshape(b, t, Hk, hd)
        q, k = _rope(q, a.rope_theta), _rope(k, a.rope_theta)
        k = jnp.repeat(k, H // Hk, axis=2)
        v = jnp.repeat(v, H // Hk, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", p, v, precision=HI)
        x = x + _mm(o.reshape(b, t, H * hd),
                    lp["attn"]["wo"].reshape(H * hd, d), quant)
        h = _rms(x, lp["norm_mlp"], a.norm_eps)
        m = lp["mlp"]
        g = jax.nn.silu(_mm(h, m["wi_gate"], quant)) * _mm(h, m["wi_up"], quant)
        return x + _mm(g, m["wo"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["norm_f"].astype(jnp.float32), a.norm_eps)


def head(params) -> jax.Array:
    return params["tok"]["unembed"]


# -- operations and bytes ------------------------------------------------------------


def layer_matmul_params(cfg: dict) -> int:
    d, f, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    q = d * cfg["n_heads"] * hd
    kv = 2 * d * cfg["n_kv_heads"] * hd
    o = cfg["n_heads"] * hd * d
    return q + kv + o + 3 * d * f


def weight_bytes(cfg: dict) -> int:
    """Layers (matmuls and two norms), final norm and LM head in bf16."""
    d, L = cfg["d_model"], cfg["n_layers"]
    head_bytes = d * cfg["vocab_size"] * BF16 + d * BF16
    return L * (layer_matmul_params(cfg) * BF16 + 2 * d * BF16) + head_bytes


def token_flops(cfg: dict, pos: int) -> float:
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    keys = min(pos + 1, flops.window(cfg))
    # scores q.k and the weighted sum p.v: 2 flops per multiply-add each
    per_layer = (2 * layer_matmul_params(cfg)
                 + 4 * cfg["n_heads"] * cfg["head_dim"] * keys)
    return float(L * per_layer + 2 * d * V)


def state_bytes(cfg: dict, pos: int) -> float:
    """k and v of every layer: the cache read, one position written."""
    per_pos = 2 * cfg["n_kv_heads"] * cfg["head_dim"] * BF16
    keys = min(pos, flops.window(cfg) - 1)
    return float(cfg["n_layers"] * per_pos * (keys + 1))


def param_count(cfg: dict) -> Dict[str, int]:
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    return {"layers": L * (layer_matmul_params(cfg) + 2 * d),
            "embed_and_head": 2 * d * V, "final_norm": d}
