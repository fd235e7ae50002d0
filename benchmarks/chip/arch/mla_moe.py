"""DeepSeek-V2 (multi-head latent attention and DeepSeekMoE) at a chip's
share of the routed experts: weights, plain reference and counts.

Written from the DeepSeek-V2 report (arXiv:2405.04434) and the published
DeepSeek-V2-Lite config.  Pre-norm blocks, RMSNorm.  Attention: queries
from a plain projection to H x (no-rope + rope) dims; a projection of x to
a latent ``c_kv`` (RMS-normed) and one rope key shared by all heads; the
latent projected up to each head's no-rope key and its value; YaRN rope
on the rope dims (inverse frequencies blended by a linear ramp between the
interpolated and the extrapolated ones; the softmax scale times YaRN's
mscale squared).  The rotation turns the two halves of the rope dims; the
published code first moves interleaved pairs into halves, a fixed
permutation of the projections' random columns.  The first
``first_k_dense`` layers have a SwiGLU MLP, the rest a float32 softmax
gate over all ``n_experts``, the top ``top_k`` chosen greedily and their
probabilities used as weights (renormalised only where the config says
so), routed SwiGLU experts and shared ones.  Untied LM head.

The reference runs a whole sequence at once in the naive form: the latent
is decompressed into per-head keys and values and attention is full and
causal.  Of the routed experts it holds the same share as the program,
``[experts_first, experts_first + experts_held)``: a routed expert not held
adds nothing, as in the program (``model-configs`` guide, section 4).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import reference
from flops import BF16
from reference import HI, _f32, _mm, _rms
from weights import Spec, _normal


def _held(cfg: dict) -> int:
    return cfg.get("experts_held") or cfg["n_experts"]


def _ones(path, shape) -> Spec:
    return (path, tuple(shape), "bfloat16", "ones", 0.0, 0.0)


# -- weights ---------------------------------------------------------------------


def _mlp_specs(prefix: str, n: int, d: int, f: int) -> List[Spec]:
    return [_normal(f"{prefix}/wi_gate", (n, d, f), d),
            _normal(f"{prefix}/wi_up", (n, d, f), d),
            _normal(f"{prefix}/wo", (n, f, d), f)]


def leaf_specs(cfg: dict) -> List[Spec]:
    """Attention and norms stacked over every layer; the leading dense
    layers' MLPs (``layers/mlp``) and the expert layers' gate, held
    experts and shared experts (``layers/moe``) stacked apart."""
    L, d, V = cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]
    H, r = cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    n0 = cfg["first_k_dense"]
    n1 = L - n0
    f, eh = cfg["moe_d_ff"], _held(cfg)
    specs = [
        ("tok/embed", (V, d), "bfloat16", "normal", 0.02, 0.0),
        _normal("tok/unembed", (d, V), d),
        _ones("norm_f", (d,)),
        _ones("layers/norm_attn", (L, d)),
        _normal("layers/attn/wq", (L, d, H, nope + rope), d),
        _normal("layers/attn/wkv_a", (L, d, r + rope), d),
        _ones("layers/attn/norm_kv", (L, r)),
        _normal("layers/attn/wkv_b", (L, r, H, nope + vd), r),
        _normal("layers/attn/wo", (L, H, vd, d), H * vd),
        _ones("layers/norm_mlp", (L, d)),
    ]
    if n0:
        specs += _mlp_specs("layers/mlp", n0, d, cfg["d_ff"])
    return specs + [
        _normal("layers/moe/router", (n1, d, cfg["n_experts"]), d),
        _normal("layers/moe/wi_gate", (n1, eh, d, f), d),
        _normal("layers/moe/wi_up", (n1, eh, d, f), d),
        _normal("layers/moe/wo", (n1, eh, f, d), f),
        *_mlp_specs("layers/moe/shared", n1, d,
                    f * cfg["n_shared_experts"]),
    ]


# -- reference -------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Arch(reference.Arch):
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_experts: int
    top_k: int
    experts_first: int
    experts_held: int
    norm_topk_prob: bool
    rope_theta: float
    yarn_factor: float
    yarn_original_len: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    norm_eps: float


def arch(cfg: dict) -> Arch:
    names = [f.name for f in dataclasses.fields(Arch) if f.name != "layout"]
    return Arch(layout=cfg["layout"], **{
        n: (_held(cfg) if n == "experts_held" else cfg[n]) for n in names})


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(a: Arch) -> np.ndarray:
    """YaRN's inverse frequencies of the rope dims, float64."""
    dim, base = a.qk_rope_head_dim, a.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    if a.yarn_factor <= 1:
        return extra

    def corr(rot):
        return (dim * math.log(a.yarn_original_len / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(a.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(a.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp            # 1 keeps the extrapolated frequency
    return extra / a.yarn_factor * (1.0 - mask) + extra * mask


def softmax_scale(a: Arch) -> float:
    s = (a.qk_nope_head_dim + a.qk_rope_head_dim) ** -0.5
    if a.yarn_mscale_all_dim:
        s *= _mscale(a.yarn_factor, a.yarn_mscale_all_dim) ** 2
    return s


def _rope(x: jax.Array, a: Arch) -> jax.Array:
    """x (B, T, H, rope): rotate the two halves by position."""
    t = x.shape[1]
    ang = np.arange(t)[:, None] * yarn_inv_freq(a)[None]
    m = (_mscale(a.yarn_factor, a.yarn_mscale)
         / _mscale(a.yarn_factor, a.yarn_mscale_all_dim))
    cos = jnp.asarray((np.cos(ang) * m).astype(np.float32))[None, :, None]
    sin = jnp.asarray((np.sin(ang) * m).astype(np.float32))[None, :, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, m, quant):
    g = jax.nn.silu(_mm(h, m["wi_gate"], quant)) * _mm(h, m["wi_up"], quant)
    return _mm(g, m["wo"], quant)


def _attention(x, lp, a: Arch, mask, quant):
    b, t, d = x.shape
    H, r = a.n_heads, a.kv_lora_rank
    nope, rope, vd = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
    at = lp["attn"]
    h = _rms(x, lp["norm_attn"], a.norm_eps)
    q = _mm(h, at["wq"].reshape(d, H * (nope + rope)), quant)
    q = q.reshape(b, t, H, nope + rope)
    kv = _mm(h, at["wkv_a"], quant)
    c = _rms(kv[..., :r], at["norm_kv"], a.norm_eps)
    k_pe = _rope(kv[..., None, r:], a)                      # (b, t, 1, rope)
    up = _mm(c, at["wkv_b"].reshape(r, H * (nope + vd)), quant)
    up = up.reshape(b, t, H, nope + vd)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_pe, (b, t, H, rope))], -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], a)], -1)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) * softmax_scale(a)
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, up[..., nope:], precision=HI)
    return x + _mm(o.reshape(b, t, H * vd), at["wo"].reshape(H * vd, d), quant)


def gate(h, router, a: Arch, quant=None):
    """(weights (..., top_k), chosen experts (..., top_k)) of the float32
    softmax gate over all experts."""
    probs = jax.nn.softmax(_mm(h, router, quant), axis=-1)
    w, idx = jax.lax.top_k(probs, a.top_k)
    if a.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, idx


def moe_layer(h, m, a: Arch, quant=None):
    """The held share of the routed experts plus the shared experts, on
    normed inputs h (..., d)."""
    w, idx = gate(h, m["router"], a, quant)
    out = _swiglu(h, m["shared"], quant)
    for e in range(a.experts_held):
        we = jnp.sum(jnp.where(idx == a.experts_first + e, w, 0.0), -1)
        ex = {k: m[k][e] for k in ("wi_gate", "wi_up", "wo")}
        out = out + we[..., None] * _swiglu(h, ex, quant)
    return out


def hidden(params, tokens, a: Arch, quant=None) -> jax.Array:
    """Final-normed hidden states (B, T, d)."""
    t = tokens.shape[1]
    x = params["tok"]["embed"][tokens].astype(jnp.float32)
    mask = jnp.asarray(np.tril(np.ones((t, t), bool)))
    layers = params["layers"]
    attn = {k: layers[k] for k in ("norm_attn", "attn", "norm_mlp")}
    n0 = jax.tree.leaves(layers["mlp"])[0].shape[0] if "mlp" in layers else 0

    def layer(ffn):
        def body(x, lp):
            lp = _f32(lp)
            x = _attention(x, lp, a, mask, quant)
            h = _rms(x, lp["norm_mlp"], a.norm_eps)
            return x + ffn(h, lp["ffn"]), None
        return body

    dense = layer(lambda h, m: _swiglu(h, m, quant))
    experts = layer(lambda h, m: moe_layer(h, m, a, quant))
    if n0:
        x, _ = jax.lax.scan(dense, x, dict(
            jax.tree.map(lambda w: w[:n0], attn), ffn=layers["mlp"]))
    x, _ = jax.lax.scan(experts, x, dict(
        jax.tree.map(lambda w: w[n0:], attn), ffn=layers["moe"]))
    return _rms(x, params["norm_f"].astype(jnp.float32), a.norm_eps)


def head(params) -> jax.Array:
    return params["tok"]["unembed"]


# -- operations and bytes ------------------------------------------------------------


def _attn_params(cfg: dict) -> int:
    """Matmul weights of one latent-attention block, in the absorbed form
    too: the key and value up-projections multiply each query and each
    output once."""
    d, H, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd)
            + H * vd * d)


def held_passes(cfg: dict) -> float:
    """Expected held experts a token is routed to, per expert layer:
    ``top_k`` choices over ``n_experts``, ``held`` of them here (6 x 8 / 64
    = 0.75 at the DeepSeek-V2-Lite share), if the choices spread evenly."""
    return cfg["top_k"] * _held(cfg) / cfg["n_experts"]


def layer_matmul_params(cfg: dict) -> float:
    """Weights a token multiplies through in one expert layer: attention,
    gate, shared experts and the expected held experts."""
    d, f = cfg["d_model"], cfg["moe_d_ff"]
    return (_attn_params(cfg) + d * cfg["n_experts"]
            + 3 * d * f * (cfg["n_shared_experts"] + held_passes(cfg)))


def _dense_layer_params(cfg: dict) -> int:
    return _attn_params(cfg) + 3 * cfg["d_model"] * cfg["d_ff"]


def _held_layer_params(cfg: dict) -> int:
    """Matmul weights of one expert layer as held: every held expert."""
    d, f = cfg["d_model"], cfg["moe_d_ff"]
    return (_attn_params(cfg) + d * cfg["n_experts"]
            + 3 * d * f * (cfg["n_shared_experts"] + _held(cfg)))


def _norms(cfg: dict) -> int:
    return 2 * cfg["d_model"] + cfg["kv_lora_rank"]


def weight_bytes(cfg: dict) -> int:
    """Every layer as held (all held experts, read once per call whatever
    the batch), the norms, the final norm and the LM head in bf16."""
    n0 = cfg["first_k_dense"]
    n1 = cfg["n_layers"] - n0
    d, V = cfg["d_model"], cfg["vocab_size"]
    return BF16 * (n0 * (_dense_layer_params(cfg) + _norms(cfg))
                   + n1 * (_held_layer_params(cfg) + _norms(cfg))
                   + d * V + d)


def token_flops(cfg: dict, pos: int) -> float:
    """Every matmul once, the expected held experts, and the absorbed
    attention over ``pos + 1`` cached rows: per head and row, the score
    against c_kv and k_pe and the weighted sum of c_kv."""
    n0 = cfg["first_k_dense"]
    n1 = cfg["n_layers"] - n0
    H, r = cfg["n_heads"], cfg["kv_lora_rank"]
    row = r + cfg["qk_rope_head_dim"]
    attend = 2 * H * (row + r) * (pos + 1)
    return float(2 * (n0 * _dense_layer_params(cfg)
                      + n1 * layer_matmul_params(cfg))
                 + cfg["n_layers"] * attend
                 + 2 * cfg["d_model"] * cfg["vocab_size"])


def state_bytes(cfg: dict, pos: int) -> float:
    """One latent row (c_kv and the rope key) per position per layer in
    bf16: ``pos`` rows read and one written."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return float(cfg["n_layers"] * row * BF16 * (pos + 1))


def param_count(cfg: dict) -> Dict[str, int]:
    n0 = cfg["first_k_dense"]
    n1 = cfg["n_layers"] - n0
    d, V = cfg["d_model"], cfg["vocab_size"]
    return {"layers": n0 * _dense_layer_params(cfg)
            + n1 * _held_layer_params(cfg) + cfg["n_layers"] * _norms(cfg),
            "embed_and_head": 2 * d * V, "final_norm": d}
