"""RWKV-6 (Finch) as the repository writes it: weights, plain reference and
counts.

RMSNorm in place of the paper's LayerNorm, token shift, data-dependent
decay ``w = exp(-exp(base + tanh(x_w A) B))``, bonus ``u`` on the current
token, a per-head group norm (eps 1e-5) gated by ``silu(g)``, and the
squared-ReLU channel mix; untied LM head.  (The paper's data-dependent
token-shift LoRA is absent from the repository's block, and so from this
reference.)  Heads of 64, decay LoRA of rank 32; the per-channel decay and
bonus vectors are kept in float32, the rest in bf16.  The reference runs
one token at a time through the recurrence.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp

import reference
from flops import BF16, F32
from reference import HI, _f32, _mm, _rms
from weights import Spec, _normal

HEAD_DIM = 64
LORA_RANK = 32


# -- weights ---------------------------------------------------------------------


def leaf_specs(cfg: dict) -> List[Spec]:
    L, d, f, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    r = LORA_RANK
    mix = [(f"layers/rwkv/mu_{n}", (L, d), "bfloat16", "uniform", 0.0, 1.0)
           for n in ("r", "k", "v", "w", "g", "ck", "cr")]
    return [
        ("tok/embed", (V, d), "bfloat16", "normal", 0.02, 0.0),
        _normal("tok/unembed", (d, V), d),
        ("norm_f", (d,), "bfloat16", "ones", 0.0, 0.0),
        ("layers/ln1", (L, d), "bfloat16", "ones", 0.0, 0.0),
        ("layers/ln2", (L, d), "bfloat16", "ones", 0.0, 0.0),
        *mix,
        *[_normal(f"layers/rwkv/{n}", (L, d, d), d)
          for n in ("wr", "wk", "wv", "wg", "wo_tm", "cr")],
        ("layers/rwkv/decay_base", (L, d), "float32", "uniform", -8.0, -4.0),
        _normal("layers/rwkv/lora_a_decay", (L, d, r), d),
        _normal("layers/rwkv/lora_b_decay", (L, r, d), r),
        ("layers/rwkv/bonus_u", (L, d), "float32", "normal", 0.1, 0.0),
        ("layers/rwkv/ln_x", (L, d), "bfloat16", "ones", 0.0, 0.0),
        _normal("layers/rwkv/ck", (L, d, f), d),
        _normal("layers/rwkv/cv", (L, f, d), f),
    ]


# -- reference -------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Arch(reference.Arch):
    d_model: int
    norm_eps: float


def arch(cfg: dict) -> Arch:
    return Arch(layout=cfg["layout"], d_model=cfg["d_model"],
                norm_eps=cfg["norm_eps"])


def _shift(x):
    """Previous token's input; the first token's is zero (a fresh slot)."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def hidden(params, tokens, a: Arch, quant=None) -> jax.Array:
    """Final-normed hidden states (B, T, d)."""
    b, t = tokens.shape
    d, K = a.d_model, HEAD_DIM
    H = d // K
    x = params["tok"]["embed"][tokens].astype(jnp.float32)

    def wkv(r, k, v, w, u):
        """The recurrence, token by token: y_t = r_t (S + u k_t v_t^T),
        S <- diag(w_t) S + k_t v_t^T, per head."""
        def step(S, inp):
            rt, kt, vt, wt = inp                             # (B, H, K)
            kv = kt[..., :, None] * vt[..., None, :]         # (B, H, K, V)
            y = jnp.einsum("bhk,bhkv->bhv", rt, S + u[None, :, :, None] * kv,
                           precision=HI)
            return S * wt[..., None] + kv, y
        seq = [jnp.moveaxis(z.reshape(b, t, H, K), 1, 0) for z in (r, k, v, w)]
        _, y = jax.lax.scan(step, jnp.zeros((b, H, K, K), jnp.float32),
                            tuple(seq))
        return jnp.moveaxis(y, 0, 1)                          # (B, T, H, V)

    def layer(x, lp):
        lp = _f32(lp)
        p = lp["rwkv"]
        h = _rms(x, lp["ln1"], a.norm_eps)
        hh = _shift(h)
        mix = lambda mu: h + (hh - h) * mu
        r = _mm(mix(p["mu_r"]), p["wr"], quant)
        k = _mm(mix(p["mu_k"]), p["wk"], quant)
        v = _mm(mix(p["mu_v"]), p["wv"], quant)
        g = _mm(mix(p["mu_g"]), p["wg"], quant)
        lora = _mm(jnp.tanh(_mm(mix(p["mu_w"]), p["lora_a_decay"], quant)),
                   p["lora_b_decay"], quant)
        w = jnp.exp(-jnp.exp(p["decay_base"] + lora))
        y = wkv(r, k, v, w, p["bonus_u"].reshape(H, K))
        mean = jnp.mean(y, -1, keepdims=True)
        var = jnp.var(y, -1, keepdims=True)
        y = ((y - mean) * jax.lax.rsqrt(var + 1e-5)).reshape(b, t, d)
        y = y * p["ln_x"] * jax.nn.silu(g)
        x = x + _mm(y, p["wo_tm"], quant)
        h = _rms(x, lp["ln2"], a.norm_eps)
        hh = _shift(h)
        mix = lambda mu: h + (hh - h) * mu
        kk = jnp.square(jax.nn.relu(_mm(mix(p["mu_ck"]), p["ck"], quant)))
        rr = jax.nn.sigmoid(_mm(mix(p["mu_cr"]), p["cr"], quant))
        return x + rr * _mm(kk, p["cv"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["norm_f"].astype(jnp.float32), a.norm_eps)


def head(params) -> jax.Array:
    return params["tok"]["unembed"]


# -- operations and bytes ------------------------------------------------------------


def layer_matmul_params(cfg: dict) -> int:
    """r, k, v, g, output and the channel mix's receptance (d x d each),
    the decay LoRA, and the channel mix's key and value."""
    d, f = cfg["d_model"], cfg["d_ff"]
    return 6 * d * d + 2 * d * LORA_RANK + 2 * d * f


def weight_bytes(cfg: dict) -> int:
    """Layers, final norm and LM head: mu x7, ln_x, ln1, ln2 in bf16,
    decay_base and bonus_u in float32."""
    d, L = cfg["d_model"], cfg["n_layers"]
    head_bytes = d * cfg["vocab_size"] * BF16 + d * BF16
    per_layer = layer_matmul_params(cfg) * BF16 + 10 * d * BF16 + 2 * d * F32
    return L * per_layer + head_bytes


def token_flops(cfg: dict, pos: int) -> float:
    """Every weight matmul, the WKV update and the LM head; the same at
    every position."""
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    # per head: k (x) v, S + u*kv, r.(.), S*w + kv over a K x V state
    h = d // HEAD_DIM
    per_layer = 2 * layer_matmul_params(cfg) + 7 * h * HEAD_DIM * HEAD_DIM
    return float(L * per_layer + 2 * d * V)


def state_bytes(cfg: dict, pos: int) -> float:
    """The float32 WKV state and two token shifts of every layer, read
    and written."""
    h = cfg["d_model"] // HEAD_DIM
    wkv = h * HEAD_DIM * HEAD_DIM * F32
    shifts = 2 * cfg["d_model"] * F32
    return float(cfg["n_layers"] * 2 * (wkv + shifts))


def param_count(cfg: dict) -> Dict[str, int]:
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    return {"layers": L * (layer_matmul_params(cfg) + 12 * d),
            "embed_and_head": 2 * d * V, "final_norm": d}
