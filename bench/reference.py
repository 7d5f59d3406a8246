"""Plain float32 reference of the served decoder, and its int8 control.

Straightforward ``jax.numpy``: every block-circulant table is expanded to
its dense blocks and every matmul runs in float32 at "highest" precision.
It imports nothing of the program under test. It reads the weight tree the
benchmark made (``weights.py``) by its key names, and the configuration
file's published numbers.

The math, per layer (pre-norm decoder, as the program defines it):

    h = rms(x) * (1 + ln1);  q, k, v = h Wq^T, h Wk^T, h Wv^T
    q, k = rms(q) * (1 + q_norm), rms(k) * (1 + k_norm)   # if qk_norm
    q, k = rope(q), rope(k)        # half-split rotation, base rope_theta
    x += softmax(q k^T / sqrt(hd) + causal) v  Wo^T      # GQA: head h
                                                         # reads kv h // G
    h = rms(x) * (1 + ln2);  x += (silu(h Wi^T) * (h Wu^T)) Wo^T

with the embedding scaled by ``sqrt(d)`` on lookup and the head tied to
the embedding or separate (``tie_word_embeddings``). Two departures from
the published Qwen3 / DeepSeek-LLM definitions are the program's and the
reference keeps them: norms scale by ``1 + w`` (not ``w``) and the
embedding is multiplied by ``sqrt(d)``.

A block-circulant table ``w (p, q, k)`` stands for the dense matrix with
``W[i*k + a, j*k + b] = w[i, j, (a - b) mod k]``.

``fmt="int8"`` is the control: the same forward with both operands of
every matmul (projections, attention scores and values, the head) rounded
to int8, symmetric, weights per output row and activations per row.

Everything runs in blocks of sequences, layer by layer inside a scan, so
it fits beside nothing else on one chip; the head runs in chunks of
positions so the full ``(S, V)`` logits are never held.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["circulant_dense", "hidden", "gaps", "served_gaps"]

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def circulant_dense(w: jax.Array) -> jax.Array:
    """``(..., p, q, k)`` block table -> dense ``(..., p*k, q*k)``."""
    p, q, k = w.shape[-3:]
    a = jnp.arange(k)
    blocks = w[..., (a[:, None] - a[None, :]) % k]        # (..., p, q, k, k)
    blocks = jnp.swapaxes(blocks, -3, -2)                 # (..., p, k, q, k)
    return blocks.reshape(*w.shape[:-3], p * k, q * k)


def _int8(x: jax.Array, axis) -> jax.Array:
    """Symmetric int8 rounding with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _rnd(x, fmt, axis=-1):
    return _int8(x, axis) if fmt == "int8" else x


def _proj(x: jax.Array, w: jax.Array, fmt: str) -> jax.Array:
    """``x (..., in)`` through a circulant ``(p, q, k)`` or dense
    ``(in, out)`` table."""
    w = w.astype(F32)
    W = circulant_dense(w) if w.ndim == 3 else w.T         # (out, in)
    return jnp.einsum("...i,oi->...o", _rnd(x, fmt), _rnd(W, fmt),
                      precision=HI)


def _rms(x, scale, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + scale.astype(F32)))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * freqs                # (S, hd/2)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(cfg: Dict, fmt: str, x, p):
    B, S, d = x.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(S)
    m = p["mixer"]
    h = _rms(x, p["ln1"]["scale"], eps)
    q = _proj(h, m["q"]["w"], fmt).reshape(B, S, H, hd)
    k = _proj(h, m["k"]["w"], fmt).reshape(B, S, Hkv, hd)
    v = _proj(h, m["v"]["w"], fmt).reshape(B, S, Hkv, hd)
    if cfg["qk_norm"]:
        q = _rms(q, m["q_norm"]["scale"], eps)
        k = _rms(k, m["k_norm"]["scale"], eps)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", _rnd(q, fmt), _rnd(k, fmt),
                   precision=HI) * hd ** -0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _rnd(a, fmt), _rnd(v, fmt),
                   precision=HI).reshape(B, S, H * hd)
    x = x + _proj(o, m["o"]["w"], fmt)
    f = p["ffn_dense"]
    h = _rms(x, p["ln2"]["scale"], eps)
    g = jax.nn.silu(_proj(h, f["wi"]["w"], fmt)) * _proj(h, f["wu"]["w"], fmt)
    return x + _proj(g, f["wo"]["w"], fmt), None


def _stack(weights) -> Dict:
    """The layer-stacked subtree: one group of one layer repeated."""
    groups = [k for k in weights if k.startswith("group")]
    if groups != ["group0"] or list(weights["group0"]) != ["l0"]:
        raise ValueError(f"reference expects one repeated layer, got "
                         f"{groups} / {list(weights.get('group0', {}))}")
    return weights["group0"]["l0"]


def hidden(cfg: Dict, fmt: str, weights, tokens: jax.Array) -> jax.Array:
    """Final normed hidden states ``(B, S, d)`` of ``tokens (B, S)``,
    positions ``0..S-1`` (right padding does not reach earlier rows)."""
    d = cfg["hidden_size"]
    x = weights["embed"]["table"][tokens].astype(F32) * (d ** 0.5)
    x, _ = jax.lax.scan(lambda c, p: _layer(cfg, fmt, c, p), x,
                        _stack(weights))
    return _rms(x, weights["final_norm"]["scale"], cfg["rms_norm_eps"])


def _head(cfg: Dict, weights) -> jax.Array:
    """The output table as ``(V, d)`` float32."""
    if cfg["tie_word_embeddings"]:
        return weights["embed"]["table"].astype(F32)
    return weights["lm_head"]["w"].astype(F32).T


def _chunk_readings(cfg, fmt, head, h, h_ctl, nxt):
    """For one chunk of positions: the reference's best logit minus its
    logit for the next token, and (with a control) minus its logit for the
    control's first choice."""
    ref = jnp.einsum("bsd,vd->bsv", h, head, precision=HI)
    best = ref.max(-1)
    at = jnp.take_along_axis(ref, nxt[..., None], -1)[..., 0]
    if h_ctl is None:
        return best - at, jnp.zeros_like(best)
    ctl = jnp.einsum("bsd,vd->bsv", _rnd(h_ctl, fmt), _rnd(head, fmt),
                     precision=HI)
    pick = jnp.argmax(ctl, -1)
    return best - at, best - jnp.take_along_axis(ref, pick[..., None],
                                                 -1)[..., 0]


def gaps(cfg: Dict, weights, tokens: np.ndarray, chunk: int = 256,
         control: str = "") -> Tuple[np.ndarray, np.ndarray]:
    """Per position ``t`` of ``tokens (B, S)``: how far the reference's
    logit for ``tokens[:, t + 1]`` lies below its best (``served``), and,
    with ``control="int8"``, how far the reference's logit for the
    control's first choice lies below its best. Both ``(B, S - 1)``."""
    B, S = tokens.shape
    toks = jnp.asarray(tokens, jnp.int32)
    h = _jit_hidden(cfg_key(cfg), "f32")(weights, toks)
    h_ctl = (_jit_hidden(cfg_key(cfg), control)(weights, toks)
             if control else None)
    head = _head(cfg, weights)
    nxt = jnp.concatenate([toks[:, 1:], jnp.zeros((B, 1), jnp.int32)], 1)
    read = _jit_chunk(cfg_key(cfg), control or "f32", h_ctl is not None)
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
    """The gaps at the served tokens of each sequence.

    ``seqs[i]`` is a prompt of ``n_prompt[i]`` tokens followed by the
    tokens served for it; every served token is read at the position
    before it (the first at the prompt's last position). Sequences run
    ``batch`` at a time, right-padded to ``pad_to``. Returns ``(served,
    control)``: one array per sequence, one gap per served token."""
    served, ctl = [], []
    for b0 in range(0, len(seqs), batch):
        blk = seqs[b0: b0 + batch]
        toks = np.zeros((batch, pad_to), np.int32)
        for j, s in enumerate(blk):
            toks[j, : len(s)] = s
        a, c = gaps(cfg, weights, toks, control=control)
        for j, s in enumerate(blk):
            lo, hi = n_prompt[b0 + j] - 1, len(s) - 1
            served.append(a[j, lo:hi])
            ctl.append(c[j, lo:hi])
    return served, ctl


# -- jitted once per configuration's numbers ---------------------------------


def cfg_key(cfg: Dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "qk_norm", "rope_theta",
            "tie_word_embeddings")
    return tuple((k, cfg[k]) for k in keys)


@functools.lru_cache(maxsize=None)
def _jit_hidden(key: tuple, fmt: str):
    return jax.jit(functools.partial(hidden, dict(key), fmt))


@functools.lru_cache(maxsize=None)
def _jit_chunk(key: tuple, fmt: str, with_control: bool):
    cfg = dict(key)
    if with_control:
        return jax.jit(functools.partial(_chunk_readings, cfg, fmt))
    return jax.jit(lambda hd, h, hc, n: _chunk_readings(cfg, fmt, hd, h,
                                                        None, n))
