"""Plain float32 reference of the served DeepSeek-V2 decoder, and its int8
control.

Straightforward ``jax.numpy`` after the published ``modeling_deepseek.py``
(DeepSeek-V2-Lite): every block-circulant table is expanded to its dense
blocks, every expert is expanded from its tables and run over every token,
and every matmul runs in float32 at "highest" precision. It imports nothing
of the program under test; from the benchmark it takes ``reference.py``'s
table expansion, rounding, norm and logit readings. It reads the weight tree
the benchmark made (``weights.py``) by its key names, and the configuration
file's published numbers.

The math, per layer (pre-norm; ``H`` heads, ``dn`` / ``dr`` / ``dv`` the
nope, rope and value head widths, ``r`` the kv latent rank):

    h = rms(x) * (1 + ln1)
    q = h Wq                         -> per head [q_nope (dn), q_pe (dr)]
    [c, k_pe] = h Wkv_a;  c = rms(c) * (1 + kv_norm)
    [k_nope, v] = c Wkv_b            -> per head (dn, dv)
    q_pe, k_pe = rope(q_pe), rope(k_pe)     # YaRN; one k_pe for all heads
    x += softmax([q_nope, q_pe] . [k_nope, k_pe] * scale + causal) v  Wo
    h = rms(x) * (1 + ln2)
    x += swiglu_shared(h) + sum_{e in top-k} g_e swiglu_e(h)  # layers >= 1
    x += swiglu(h)                                            # layer 0

with ``scale = (dn + dr) ** -0.5 * mscale(factor, mscale_all_dim) ** 2``,
``mscale(s, m) = 0.1 m ln(s) + 1``, router logits ``h Wr`` in float32,
``p = softmax(logits)``, the experts the ``num_experts_per_tok`` largest
``p`` and ``g_e = p_e * routed_scaling_factor`` (divided by their sum
instead when ``norm_topk_prob``). The rotary frequencies are YaRN's
(``DeepseekV2YarnRotaryEmbedding``): the base ``theta ** (-2i / dr)``
and the base over ``factor`` blended along a linear ramp between the
correction dims of ``beta_fast`` and ``beta_slow``, cos and sin times
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.

Three departures from the published definition are the program's, and the
reference keeps them: norms scale by ``1 + w`` (not ``w``), the embedding
is multiplied by ``sqrt(d)``, and the rope dims are rotated half-split
(``[x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin]``), where the published
code first permutes interleaved pairs into halves; with random weights
that is a fixed permutation of the rows of ``q_pe`` and ``k_pe``.

``fmt="int8"`` is the control: the same forward with both operands of
every matmul (projections, router, experts, attention scores and values,
the head) rounded to int8, symmetric, weights per output row and
activations per row.

Everything runs one block of sequences at a time, layer by layer inside a
scan, the experts one at a time inside it, so it fits beside nothing else
on one chip; the head runs in chunks of positions.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference
from reference import HI, F32, _proj, _rms, _rnd

__all__ = ["rope_freqs", "hidden", "gaps", "served_gaps", "PAD"]

PAD = 1024          # sequences are right-padded to a multiple of this


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_freqs(cfg: Dict) -> Tuple[jax.Array, float]:
    """YaRN's rotary frequencies ``(dr / 2,)`` and the cos/sin factor."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    y = cfg["rope_scaling"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    inter = extra / y["factor"]

    def corr(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    lo = max(math.floor(corr(y["beta_fast"])), 0)
    hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - lo) / (hi - lo), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    return inv, (_mscale(y["factor"], y["mscale"])
                 / _mscale(y["factor"], y["mscale_all_dim"]))


def _rope(x, pos, cfg):
    """Half-split rotation of ``x (B, S, h, dr)`` at positions ``pos``."""
    inv, m = rope_freqs(cfg)
    ang = pos.astype(F32)[:, None] * inv
    c, s = (jnp.cos(ang) * m)[None, :, None], (jnp.sin(ang) * m)[None, :,
                                                                  None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(cfg: Dict, fmt: str, h, m):
    B, S, _ = h.shape
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    pos = jnp.arange(S)
    q = _proj(h, m["q"]["w"], fmt).reshape(B, S, H, dn + dr)
    ckv = _proj(h, m["kv_a"]["w"], fmt)
    c = _rms(ckv[..., :r], m["kv_norm"]["scale"], eps)
    kv = _proj(c, m["kv_b"]["w"], fmt).reshape(B, S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg)], -1)
    k_pe = _rope(ckv[:, :, None, r:], pos, cfg)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, dr))], -1)
    y = cfg["rope_scaling"]
    scale = (dn + dr) ** -0.5 * _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    s = jnp.einsum("bqhd,bkhd->bhqk", _rnd(q, fmt), _rnd(k, fmt),
                   precision=HI) * scale
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _rnd(a, fmt), _rnd(kv[..., dn:], fmt),
                   precision=HI).reshape(B, S, H * dv)
    return _proj(o, m["o"]["w"], fmt)


def _swiglu(h, f, fmt):
    g = jax.nn.silu(_proj(h, f["wi"]["w"], fmt)) * _proj(h, f["wu"]["w"], fmt)
    return _proj(g, f["wo"]["w"], fmt)


def _experts(cfg: Dict, fmt: str, h, moe):
    """The routed experts' gate-weighted sum; each expert expanded from its
    tables and run over every token, its output kept where it was chosen."""
    logits = _proj(h, moe["router"]["w"], fmt)                # (B, S, E)
    p = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    else:
        top = top * cfg["routed_scaling_factor"]
    E = cfg["n_routed_experts"]
    gate = (jax.nn.one_hot(idx, E, dtype=F32) * top[..., None]).sum(-2)

    def one(acc, xs):
        w, g = xs
        return acc + g[..., None] * _swiglu(h, w, fmt), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (moe["experts"], jnp.moveaxis(gate, -1, 0)))
    return acc


def _layer(cfg: Dict, fmt: str, x, p):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, fmt, _rms(x, p["ln1"]["scale"], eps), p["mixer"])
    h = _rms(x, p["ln2"]["scale"], eps)
    out = _swiglu(h, p["ffn_dense"], fmt)
    if "ffn_moe" in p:
        out = out + _experts(cfg, fmt, h, p["ffn_moe"])
    return x + out, None


def hidden(cfg: Dict, fmt: str, weights, tokens: jax.Array) -> jax.Array:
    """Final normed hidden states ``(B, S, d)`` of ``tokens (B, S)``,
    positions ``0..S-1`` (right padding does not reach earlier rows). The
    layer groups run in order; a group whose norms carry a leading axis is
    that many layers, scanned."""
    d = cfg["hidden_size"]
    x = weights["embed"]["table"][tokens].astype(F32) * (d ** 0.5)
    groups = sorted((k for k in weights if k.startswith("group")),
                    key=lambda k: int(k[len("group"):]))
    for g in groups:
        layers = weights[g]
        if list(layers) != ["l0"]:
            raise ValueError(f"reference expects one layer a group, got "
                             f"{list(layers)} in {g}")
        p = layers["l0"]
        if p["ln1"]["scale"].ndim == 2:
            x, _ = jax.lax.scan(lambda c, q: _layer(cfg, fmt, c, q), x, p)
        else:
            x, _ = _layer(cfg, fmt, x, p)
    return _rms(x, weights["final_norm"]["scale"], cfg["rms_norm_eps"])


def gaps(cfg: Dict, weights, tokens: np.ndarray, chunk: int = 256,
         control: str = "") -> Tuple[np.ndarray, np.ndarray]:
    """As ``reference.gaps``: per position ``t`` of ``tokens (B, S)``, how
    far the reference's logit for ``tokens[:, t + 1]`` lies below its best,
    and with ``control="int8"`` how far its logit for the control's first
    choice does. Both ``(B, S - 1)``."""
    B, S = tokens.shape
    toks = jnp.asarray(tokens, jnp.int32)
    key = json.dumps(cfg, sort_keys=True)
    h = _jit_hidden(key, "f32")(weights, toks)
    h_ctl = _jit_hidden(key, control)(weights, toks) if control else None
    head = reference._head(cfg, weights)
    nxt = jnp.concatenate([toks[:, 1:], jnp.zeros((B, 1), jnp.int32)], 1)
    read = _jit_chunk(control or "f32", h_ctl is not None)
    served, ctl = [], []
    for s0 in range(0, S, chunk):
        sl = slice(s0, min(S, s0 + chunk))
        a, b = read(head, h[:, sl], None if h_ctl is None else h_ctl[:, sl],
                    nxt[:, sl])
        served.append(np.asarray(a))
        ctl.append(np.asarray(b))
    return (np.concatenate(served, 1)[:, :-1],
            np.concatenate(ctl, 1)[:, :-1])


def served_gaps(cfg: Dict, weights, seqs, n_prompt, pad_to: int,
                batch: int, control: str = ""):
    """As ``reference.served_gaps``, with each block right-padded to the
    smallest multiple of ``PAD`` (at most ``pad_to``) that holds its
    longest sequence: a few shapes to compile, and no expert work on a
    whole cache's length of padding."""
    served, ctl = [], []
    for b0 in range(0, len(seqs), batch):
        blk = seqs[b0: b0 + batch]
        n = max(len(s) for s in blk)
        toks = np.zeros((batch, min(pad_to, -(-n // PAD) * PAD)), np.int32)
        for j, s in enumerate(blk):
            toks[j, : len(s)] = s
        a, c = gaps(cfg, weights, toks, control=control)
        for j, s in enumerate(blk):
            lo, hi = n_prompt[b0 + j] - 1, len(s) - 1
            served.append(a[j, lo:hi])
            ctl.append(c[j, lo:hi])
    return served, ctl


@functools.lru_cache(maxsize=None)
def _jit_hidden(key: str, fmt: str):
    def run(weights, toks):
        with jax.default_matmul_precision("highest"):
            return hidden(json.loads(key), fmt, weights, toks)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jit_chunk(fmt: str, with_control: bool):
    if with_control:
        return jax.jit(functools.partial(reference._chunk_readings, None, fmt))
    return jax.jit(lambda hd, h, hc, n: reference._chunk_readings(
        None, fmt, hd, h, None, n))
