"""Shared neural-net building blocks: norms, embeddings, rotary, masks."""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.module import ParamSpec

__all__ = [
    "RMSNorm",
    "Embedding",
    "rotary",
    "rope_frequencies",
    "yarn_mscale",
    "apply_rope",
    "causal_mask",
    "sliding_window_mask",
    "prefix_lm_mask",
]


@dataclasses.dataclass(frozen=True)
class RMSNorm:
    dim: int
    stack: Tuple[int, ...] = ()
    eps: float = 1e-6

    def specs(self):
        return {
            "scale": ParamSpec(
                self.stack + (self.dim,),
                jnp.float32,
                ("layers",) * len(self.stack) + (None,),
                init="zeros",   # gemma-style (1 + scale)
            )
        }

    def __call__(self, params, x: jax.Array) -> jax.Array:
        dtype = x.dtype
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + self.eps) * (1.0 + params["scale"])
        return y.astype(dtype)


@dataclasses.dataclass(frozen=True)
class Embedding:
    vocab: int
    dim: int
    dtype: str = "bfloat16"

    def specs(self):
        return {
            "table": ParamSpec(
                (self.vocab, self.dim),
                jnp.dtype(self.dtype),
                ("vocab", "embed"),
                init="normal",
                scale=1.0,
            )
        }

    def encode(self, params, tokens: jax.Array, scale_by_dim: bool = True):
        x = params["table"][tokens]
        if scale_by_dim:
            x = x * jnp.asarray(self.dim**0.5, x.dtype)
        return x

    @jax.named_scope("head")
    def decode(self, params, x: jax.Array) -> jax.Array:
        """Tied logits head: (..., d) @ (vocab, d)^T -> f32 logits."""
        return jnp.einsum(
            "...d,vd->...v", x.astype(jnp.float32),
            params["table"].astype(jnp.float32),
        )


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 m ln(s) + 1`` (1 for
    ``s <= 1``)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_frequencies(head_dim: int, theta: float, yarn=None) -> jax.Array:
    """The ``head_dim / 2`` rotary frequencies, f32. With a
    :class:`~repro.configs.base.YarnConfig` they are YaRN's blend of the
    base frequencies (high ones, kept) and the base over ``factor`` (low
    ones, interpolated), along a linear ramp over the dims between the
    correction dims of ``beta_fast`` and ``beta_slow`` rotations in
    ``original_max_position`` positions (``DeepseekV2YarnRotaryEmbedding``).
    """
    base = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                     / head_dim)
    if yarn is None:
        return base

    def corr_dim(rot):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    return base / yarn.factor * ramp + base * (1.0 - ramp)


def rotary(positions: jax.Array, head_dim: int, theta: float,
           yarn=None) -> Tuple[jax.Array, jax.Array]:
    """positions (...,S) -> cos/sin (...,S, head_dim/2), f32. With YaRN
    both carry the ratio of its ``mscale`` to ``mscale_all_dim`` factors."""
    freqs = rope_frequencies(head_dim, theta, yarn)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            c, s = c * m, s * m
    return c, s


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, S, H, D); cos/sin (B, S, D/2) or (S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention masks (log-space additive, f32)
# ---------------------------------------------------------------------------

_NEG = -2.0e38


def causal_mask(q_pos: jax.Array, kv_pos: jax.Array) -> jax.Array:
    """(..., Q), (..., K) -> (..., Q, K) additive mask."""
    ok = q_pos[..., :, None] >= kv_pos[..., None, :]
    return jnp.where(ok, 0.0, _NEG).astype(jnp.float32)


def sliding_window_mask(q_pos, kv_pos, window: int) -> jax.Array:
    d = q_pos[..., :, None] - kv_pos[..., None, :]
    ok = (d >= 0) & (d < window)
    return jnp.where(ok, 0.0, _NEG).astype(jnp.float32)


def prefix_lm_mask(q_pos, kv_pos, prefix_len: int) -> jax.Array:
    """Bidirectional over the first prefix_len positions, causal after
    (PaliGemma image-prefix masking)."""
    causal = q_pos[..., :, None] >= kv_pos[..., None, :]
    in_prefix = kv_pos[..., None, :] < prefix_len
    ok = causal | in_prefix
    return jnp.where(ok, 0.0, _NEG).astype(jnp.float32)
