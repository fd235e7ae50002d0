"""Plain float32 references of the served architectures, the comparison
of served tokens against them, and their lower-precision controls.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching tricks.  Each architecture's reference is in its
``arch/<layout>.py`` (``layouts.py``): its ``Arch``, its final-normed
hidden states and its head, following the architecture as this repository
defines it (its sizes and layer equations); a new architecture adds that
file and nothing here.  This file holds what they share (the matmul and
its control rounding, RMSNorm, rotary embedding) and ``served_gaps``.
They import nothing of the program: they run on the weights the benchmark
made, upcast to float32 one layer at a time.

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

import layouts

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    """What a layout's reference is compiled for: hashable, so that
    ``served_gaps`` takes it as a static argument.  Each
    ``arch/<layout>.py`` subclasses it with the sizes its ``hidden`` reads
    and makes it with ``arch(cfg)``."""
    layout: str

    @classmethod
    def of(cls, cfg: dict) -> "Arch":
        return layouts.of(cfg).arch(cfg)


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
    layout = layouts.module(a.layout)
    head = layout.head(params)

    def logits(quant):
        hid = layout.hidden(params, tokens, a, quant)
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
