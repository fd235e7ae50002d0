"""Plain float32 references of the two served architectures, and their
lower-precision controls.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching tricks; a whole sequence at once for the dense model,
one token at a time through the recurrence for rwkv.  They follow the
architectures as this repository defines them (its sizes and layer
equations), and import nothing of the program: they run on the weights the
benchmark made, upcast to float32 one layer at a time.

* Dense (h2o-danube-3): pre-norm blocks, RMSNorm (eps from the config),
  rotary embedding on the two halves of each head, grouped-query causal
  attention (inside the sliding window where the configuration has one),
  SwiGLU MLP, untied LM head.
* RWKV-6 (Finch) as the repository writes it: RMSNorm in place of the
  paper's LayerNorm, token shift, data-dependent decay
  ``w = exp(-exp(base + tanh(x_w A) B))``, bonus ``u`` on the current
  token, a per-head group norm (eps 1e-5) gated by ``silu(g)``, and the
  squared-ReLU channel mix.  (The paper's data-dependent token-shift LoRA
  is absent from the repository's block, and so from this reference.)

``quant`` names the control: every weight matmul with its weight rounded
per output channel and its input rounded per token, symmetric and scaled
to the format's largest value, the rest in float32 -- to int8
(``"int8"``) or to float8 e4m3 (``"fp8"``), the two steps below the
configuration's bf16 that a later change could take.  fp8 is the control
the limits are set against; int8 is read beside it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
RWKV_HEAD_DIM = 64


@dataclasses.dataclass(frozen=True)
class Arch:
    layout: str
    n_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    window: int = 0
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6

    @classmethod
    def of(cls, cfg: dict) -> "Arch":
        names = {f.name for f in dataclasses.fields(cls)}
        arch = {k: v for k, v in cfg.items() if k in names}
        if cfg.get("attn_pattern") == "full":
            arch["window"] = cfg["max_seq_len"]
        return cls(**arch)


def _round(x: jax.Array, axis: int, quant: str) -> jax.Array:
    """Symmetric rounding of ``x`` to ``quant`` with one scale per slice
    along the other axes (``axis`` is the one the scale spans)."""
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0.0, 1.0, s)
    if quant == "int8":
        return jnp.clip(jnp.round(x / s), -top, top) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    """x (..., k) @ w (k, n) in float32; a control rounds both sides."""
    if quant is not None:
        x, w = _round(x, -1, quant), _round(w, 0, quant)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (B, T, H, hd): rotate the two halves of each head by position."""
    hd, t = x.shape[-1], x.shape[1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(t, dtype=np.float32)[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang))[None, :, None]
    sin = jnp.asarray(np.sin(ang))[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def dense_hidden(params, tokens, a: Arch, quant=None) -> jax.Array:
    """Final-normed hidden states (B, T, d) of the dense model."""
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


def _shift(x):
    """Previous token's input; the first token's is zero (a fresh slot)."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def rwkv_hidden(params, tokens, a: Arch, quant=None) -> jax.Array:
    """Final-normed hidden states (B, T, d) of the rwkv model."""
    b, t = tokens.shape
    d, K = a.d_model, RWKV_HEAD_DIM
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


HIDDEN = {"dense": dense_hidden, "rwkv": rwkv_hidden}


@functools.partial(jax.jit, static_argnames=("a", "control"))
def served_gaps(params, tokens, positions, served, valid, a: Arch,
                control: Optional[str] = None) -> Dict[str, jax.Array]:
    """For each row: the widest gap (``gap``) by which a served token's
    logit lies below the reference's best, over the positions that served
    it, and the sum of those gaps (``gap_sum``).

    ``tokens`` (B, T): prompt then served tokens, padded; ``positions``
    (B, N): the position whose logits chose each served token (the last
    prompt token for the first); ``served`` (B, N) and ``valid`` (B, N).
    With ``control`` ("int8" or "fp8"), the same of the tokens that control
    puts first at the same positions (``control_gap``,
    ``control_gap_sum``)."""
    head = params["tok"]["unembed"]

    def logits(quant):
        hid = HIDDEN[a.layout](params, tokens, a, quant)
        sel = jnp.take_along_axis(hid, positions[..., None], axis=1)
        return _mm(sel, head.astype(jnp.float32), quant)      # (B, N, V)

    ref = logits(None)
    best = jnp.max(ref, -1)

    def gaps(tokens):
        g = best - jnp.take_along_axis(ref, tokens[..., None], -1)[..., 0]
        g = jnp.where(valid, g, 0.0)
        return jnp.max(g, -1), jnp.sum(g, -1)

    out = dict(zip(("gap", "gap_sum"), gaps(served)))
    if control is not None:
        pick = jnp.argmax(logits(control), -1).astype(served.dtype)
        out.update(zip(("control_gap", "control_gap_sum"), gaps(pick)))
    return out


def pack(rows, length: int, n_out: int, batch: int
         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad (prompt, served) pairs into ``served_gaps``' arrays: ``batch``
    rows of ``length`` tokens and ``n_out`` served positions."""
    tokens = np.zeros((batch, length), np.int32)
    positions = np.zeros((batch, n_out), np.int32)
    served = np.zeros((batch, n_out), np.int32)
    valid = np.zeros((batch, n_out), bool)
    for i, (prompt, out) in enumerate(rows):
        seq = list(prompt) + list(out[:-1])
        if len(seq) > length or len(out) > n_out:
            raise ValueError(f"row {i} does not fit {length} x {n_out}")
        tokens[i, : len(seq)] = seq
        n = len(out)
        positions[i, :n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        served[i, :n] = out
        valid[i, :n] = True
    return tokens, positions, served, valid
