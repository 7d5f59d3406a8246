"""Grouped-query attention: rotary, qk-norm, sliding window, KV cache, flash.

Covers every assigned attention variant:
  * GQA / MQA (n_kv_heads ∈ {1..n_heads})
  * qk-norm (qwen3), attention logit softcapping (config)
  * gemma3 local:global interleave — local layers use a sliding-window mask
    and, in decode, a **ring-buffer KV cache of window size** (5/6 of gemma3
    layers hold 1024-entry caches instead of 524k — this is what makes the
    long_500k cell feasible)
  * prefix-LM masking (paligemma) and bidirectional encoders (seamless-m4t)
  * cross-attention (enc-dec) — KV cached once from the encoder

Masks are never materialized globally: they are predicates over absolute
positions evaluated per score tile. Long sequences (train_4k / prefill_32k)
use a **flash-style chunked attention** — lax.scan over KV chunks with
running (max, sum, acc) — so peak memory is O(S·chunk) not O(S²). The KV
cache stores absolute positions alongside k/v, so ring-buffer wraparound
masks stale slots exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.latent_attention import latent_decode_attention
from repro.nn.layers import RMSNorm, apply_rope, rotary, yarn_mscale
from repro.nn.linear import Linear

__all__ = ["Attention", "MLAttention", "init_kv_cache", "init_mla_cache",
           "mla_cache_width", "write_cache", "flash_attention",
           "pool_index", "pool_rows", "MLA_DECODE_CHUNK"]

_NEG = -2.0e38


def init_kv_cache(batch, cache_len, n_kv, head_dim, dtype):
    """Empty cache; pos = -1 marks an unfilled (always-masked) slot."""
    return {
        "k": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "pos": -jnp.ones((batch, cache_len), jnp.int32),
    }


def pool_index(layer, *idx):
    """Index into a slot-pool leaf: ``idx`` from the slot axis on, behind
    the leading repeat axis at ``layer`` when the pool is repeat-stacked
    (``layer`` is None for a plain group)."""
    return idx if layer is None else (layer,) + idx


def pool_rows(leaf, layer, slot_idx):
    """Rows ``slot_idx`` of a slot-pool leaf (of layer ``layer`` when the
    pool is repeat-stacked). The layer is sliced first and the rows
    gathered from that: on a TPU the compiler then streams the rows into
    their consumer, where one gather over both axes materializes a copy."""
    return (leaf if layer is None else leaf[layer])[slot_idx]


# ---------------------------------------------------------------------------
# Position-predicate masks (computed per tile, never O(S²) global)
# ---------------------------------------------------------------------------


def _mask_bias(
    q_pos: jax.Array,           # (B, Sq)
    kv_pos: jax.Array,          # (B, Skv)
    *,
    causal: bool,
    window: int,
    prefix_len: int,
) -> jax.Array:
    """(B, Sq, Skv) additive f32 bias from position predicates."""
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    ok = kp >= 0                               # valid cache slots
    if causal:
        c = kp <= qp
        if prefix_len > 0:                     # prefix-LM: bidir over prefix
            c = c | (kp < prefix_len)
        ok = ok & c
    if window > 0:
        ok = ok & (qp - kp < window)
    return jnp.where(ok, 0.0, _NEG).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Flash-style chunked attention (XLA-level; O(S·chunk) memory)
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,               # (B, Sq, HKV, G, hd)
    k: jax.Array,               # (B, Skv, HKV, hd)
    v: jax.Array,               # (B, Skv, HKV, hd)
    q_pos: jax.Array,           # (B, Sq)
    kv_pos: jax.Array,          # (B, Skv)
    *,
    causal: bool,
    window: int = 0,
    prefix_len: int = 0,
    softcap: float = 0.0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Lazy-softmax attention over KV chunks. Returns (B, Sq, HKV, G, hv)
    (``hv``: v's head width, which may differ from q's and k's ``hd``);
    scores scale by ``scale``, default ``hd ** -0.5``."""
    B, Sq, HKV, G, hd = q.shape
    hv = v.shape[-1]
    Skv = k.shape[1]
    scale = hd**-0.5 if scale is None else scale
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # pad to multiples
    pq = (-Sq) % q_chunk
    pk = (-Skv) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pq)), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pk)), constant_values=-1)
    nq, nk = q.shape[1] // q_chunk, k.shape[1] // kv_chunk

    qs = q.reshape(B, nq, q_chunk, HKV, G, hd)
    qp = q_pos.reshape(B, nq, q_chunk)
    Skv_pad = k.shape[1]

    # Sliding-window KV-span slicing: a q chunk starting at position s only
    # attends to KV in [s + qc - 1 - window + 1, s + qc - 1]; with aligned
    # positions each q chunk needs a FIXED-SIZE span (window + q_chunk,
    # rounded to kv_chunk) at a dynamic offset — static shapes, 1/(S/span)
    # of the fully-masked chunk compute skipped (gemma3's 52/62 local
    # layers: ~16× less attention work at 32k prefill).
    aligned = bool(window) and causal and Sq == Skv and prefix_len == 0
    if aligned:
        span = min(Skv_pad,
                   ((window + q_chunk + kv_chunk - 1) // kv_chunk) * kv_chunk)
    else:
        span = Skv_pad
    n_span = span // kv_chunk

    def q_block(qi, qpi, qidx):
        if aligned and span < Skv_pad:
            start = jnp.clip(qidx * q_chunk + q_chunk - span, 0,
                             Skv_pad - span)
            ks = jax.lax.dynamic_slice_in_dim(k, start, span, 1)
            vs_ = jax.lax.dynamic_slice_in_dim(v, start, span, 1)
            kp_ = jax.lax.dynamic_slice_in_dim(kv_pos, start, span, 1)
        else:
            ks, vs_, kp_ = k, v, kv_pos
        ks = ks.reshape(B, n_span, kv_chunk, HKV, hd)
        vs_ = vs_.reshape(B, n_span, kv_chunk, HKV, hv)
        kp_ = kp_.reshape(B, n_span, kv_chunk)

        # qi (B, qc, HKV, G, hd); scan over kv chunks
        def kv_step(carry, xs):
            m, l, acc = carry
            ki, vi, kpi = xs                    # (B,kc,HKV,hd),(...),(B,kc)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi, ki,
                preferred_element_type=jnp.float32,
            ) * scale
            if softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            bias = _mask_bias(qpi, kpi, causal=causal, window=window,
                              prefix_len=prefix_len)
            s = s + bias[:, None, None, :, :]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(qi.dtype), vi,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, HKV, G, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, HKV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, HKV, G, q_chunk, hv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs_, 1, 0),
             jnp.moveaxis(kp_, 1, 0)),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.transpose(out, (0, 3, 1, 2, 4))  # (B, qc, HKV, G, hd)

    outs = jax.lax.map(
        lambda xs: q_block(*xs),
        (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(qp, 1, 0),
         jnp.arange(nq)),
    )                                               # (nq, B, qc, HKV, G, hd)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * q_chunk, HKV, G, hv)
    return out[:, :Sq].astype(q.dtype)


def _direct_attention(q, k, v, q_pos, kv_pos, *, causal, window, prefix_len,
                      softcap, scale=None):
    """Small-Sq path (decode): one materialized score tensor."""
    B, Sq, HKV, G, hd = q.shape
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * (hd**-0.5 if scale is None else scale)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    bias = _mask_bias(q_pos, kv_pos, causal=causal, window=window,
                      prefix_len=prefix_len)
    s = s + bias[:, None, None, :, :]
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(q.dtype), v)
    return out


# ---------------------------------------------------------------------------
# The attention layer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Attention:
    cfg: ModelConfig
    local: bool = False            # sliding-window variant
    cross: bool = False            # enc-dec cross attention
    causal: bool = True            # False for encoder self-attn
    prefix_len: int = 0            # VLM prefix-LM bidirectional span
    stack: Tuple[int, ...] = ()

    # -- projections ----------------------------------------------------
    def _proj(self, i, o, oa):
        return Linear(in_dim=i, out_dim=o, in_axis="embed", out_axis=oa,
                      family="attn", swm=self.cfg.swm, stack=self.stack,
                      dtype=self.cfg.param_dtype)

    @property
    def q_proj(self):
        return self._proj(self.cfg.d_model, self.cfg.n_heads * self.cfg.head_dim, "heads")

    @property
    def k_proj(self):
        return self._proj(self.cfg.d_model, self.cfg.n_kv_heads * self.cfg.head_dim, "kv_heads")

    @property
    def v_proj(self):
        return self._proj(self.cfg.d_model, self.cfg.n_kv_heads * self.cfg.head_dim, "kv_heads")

    @property
    def o_proj(self):
        return Linear(in_dim=self.cfg.n_heads * self.cfg.head_dim,
                      out_dim=self.cfg.d_model, in_axis="heads",
                      out_axis="embed", family="attn", swm=self.cfg.swm,
                      stack=self.stack, dtype=self.cfg.param_dtype)

    def specs(self):
        s = {"q": self.q_proj.specs(), "k": self.k_proj.specs(),
             "v": self.v_proj.specs(), "o": self.o_proj.specs()}
        if self.cfg.qk_norm:
            hd = self.cfg.head_dim
            s["q_norm"] = RMSNorm(hd, stack=self.stack).specs()
            s["k_norm"] = RMSNorm(hd, stack=self.stack).specs()
        return s

    def _fused_qkv(self, params, x):
        """Q/K/V as ONE stacked-p circulant launch when all three tables are
        circulant with one block size (they share the input x, so the
        forward transform of x and the kernel pipeline are amortized 3-way).
        Returns (q, k, v) flat projections or None when not fusable.

        Frozen (serve) trees carry the pre-concatenated stacked table that
        ``plan.freeze_params`` attaches under ``plan.FUSED_KEY`` — the
        launch then reads one resident (Σp_i, q, K) table and its trace
        contains no weight-side concatenate."""
        qp, kp, vp = self.q_proj, self.k_proj, self.v_proj
        kb = qp.block_size
        if not (qp.is_circulant and kp.is_circulant and vp.is_circulant
                and kp.block_size == kb and vp.block_size == kb):
            return None
        from repro.core import circulant as circ
        from repro.kernels.block_circulant.plan import FUSED_KEY

        fused = params.get(FUSED_KEY)
        if fused is not None:
            return circ.block_circulant_apply_multi(
                x, None, impl=self.cfg.swm.impl,
                w_freq_cat=(fused["wr"], fused["wi"]),
                w_scale_cat=fused.get("w_scale"),
                splits=tuple(p.out_dim // kb for p in (qp, kp, vp)),
                k=kb, karatsuba=self.cfg.swm.karatsuba,
            )
        names = ("q", "k", "v")
        frozen = all("wr" in params[n] and "wi" in params[n] for n in names)
        return circ.block_circulant_apply_multi(
            x,
            None if frozen else [params[n]["w"] for n in names],
            impl=self.cfg.swm.impl,
            # int8 per-projection tables dequantize here (the multi path
            # concatenates to complex64, which must see f32 tables)
            w_freqs=([circ.dequantize_freq_pair(
                params[n]["wr"], params[n]["wi"], params[n].get("w_scale"))
                for n in names] if frozen else None),
            k=kb,
            karatsuba=self.cfg.swm.karatsuba,
        )

    @property
    def window(self) -> int:
        return self.cfg.sliding_window if self.local else 0

    def _rope_theta(self) -> float:
        return self.cfg.rope_theta_local if self.local else self.cfg.rope_theta

    # -- forward ---------------------------------------------------------
    @jax.named_scope("attention")
    def __call__(
        self,
        params,
        x: jax.Array,                       # (B, S, D)
        positions: jax.Array,               # (B, S)
        *,
        cache: Optional[dict] = None,
        kv_x: Optional[jax.Array] = None,   # cross-attn source
        kv_positions: Optional[jax.Array] = None,
        update_cache: bool = True,
        slot_idx: Optional[jax.Array] = None,
        layer: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[dict]]:
        """With ``slot_idx`` the cache is the whole slot pool (decode in
        place): batch row ``b`` is pool row ``slot_idx[b]`` (behind the
        repeat axis at ``layer``), only the new entries are written, and
        attention reads the rows straight from the pool. Returns the
        updated pool."""
        cfg = self.cfg
        B, S, _ = x.shape
        hd, HQ, HKV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
        G = HQ // HKV

        qkv = self._fused_qkv(params, x) if kv_x is None and not self.cross \
            else None
        if qkv is not None:
            qh, kh, vh = qkv
            q = qh.reshape(B, S, HQ, hd)
            k = kh.reshape(B, S, HKV, hd)
            v = vh.reshape(B, S, HKV, hd)
        else:
            q = self.q_proj(params["q"], x).reshape(B, S, HQ, hd)
            if self.cross and cache is not None and kv_x is None:
                k = v = None                 # cross-attn decode: KV from cache
            else:
                src = x if kv_x is None else kv_x
                k = self.k_proj(params["k"], src).reshape(B, src.shape[1], HKV, hd)
                v = self.v_proj(params["v"], src).reshape(B, src.shape[1], HKV, hd)

        if cfg.qk_norm:
            q = RMSNorm(hd, stack=self.stack)(params["q_norm"], q)
            if k is not None:
                k = RMSNorm(hd, stack=self.stack)(params["k_norm"], k)

        if not self.cross:
            theta = self._rope_theta()
            qc, qs = rotary(positions, hd, theta)
            q = apply_rope(q, qc, qs)
            if k is not None:
                kpos = positions if kv_positions is None else kv_positions
                kc, ks = rotary(kpos, hd, theta)
                k = apply_rope(k, kc, ks)

        new_cache = None
        if cache is not None:
            if self.cross:
                if k is not None and update_cache:   # prefill: stash enc KV
                    new_cache = {"k": k.astype(cache["k"].dtype),
                                 "v": v.astype(cache["v"].dtype),
                                 "pos": kv_positions.astype(jnp.int32)}
                else:
                    new_cache = cache
                k_att = new_cache["k"].astype(x.dtype)
                v_att = new_cache["v"].astype(x.dtype)
                kv_pos = new_cache["pos"]
            elif slot_idx is not None:
                new_cache = write_cache(cache, {"k": k, "v": v}, positions,
                                        slot_idx, layer)
                k_att = pool_rows(new_cache["k"], layer, slot_idx).astype(
                    x.dtype)
                v_att = pool_rows(new_cache["v"], layer, slot_idx).astype(
                    x.dtype)
                kv_pos = pool_rows(new_cache["pos"], layer, slot_idx)
            else:
                new_cache = write_cache(cache, {"k": k, "v": v}, positions)
                if S == 1 or S < cache["k"].shape[1]:
                    # decode / short append: attend over the cache
                    k_att = new_cache["k"].astype(x.dtype)
                    v_att = new_cache["v"].astype(x.dtype)
                    kv_pos = new_cache["pos"]
                else:
                    # prefill covering the whole cache: attend over fresh kv
                    k_att, v_att, kv_pos = k, v, positions
        else:
            k_att, v_att, kv_pos = k, v, (
                positions if kv_positions is None else kv_positions
            )

        causal = self.causal and not self.cross
        if S > cfg.flash_q_chunk:
            out = flash_attention(
                q.reshape(B, S, HKV, G, hd), k_att, v_att, positions, kv_pos,
                causal=causal, window=self.window, prefix_len=self.prefix_len,
                softcap=cfg.logit_softcap,
                q_chunk=cfg.flash_q_chunk, kv_chunk=cfg.flash_kv_chunk,
            )
        else:
            out = _direct_attention(
                q.reshape(B, S, HKV, G, hd), k_att, v_att, positions, kv_pos,
                causal=causal, window=self.window, prefix_len=self.prefix_len,
                softcap=cfg.logit_softcap,
            )
        out = self.o_proj(params["o"], out.reshape(B, S, HQ * hd))
        return out, new_cache


@jax.named_scope("kv_move")
def write_cache(cache, new, positions, slot_idx=None, layer=None):
    """Write the new entries ``new`` (leaf name -> ``(B, S, ...)``) and
    their positions into ``cache``. Ring-buffer write at slot = pos %
    ring_len, the leaf's own length (``attn_local`` rings are shorter than
    ``cache_len``). Batch row ``b`` writes cache row ``b``, or pool row
    ``slot_idx[b]`` (behind the repeat axis at ``layer``) when decoding in
    place on the slot pool. If the incoming span exceeds the ring, only the
    trailing ring_len tokens are written (their slots are unique, so the
    scatter is well-defined)."""
    B, S = positions.shape
    cache_len = cache["pos"].shape[-1]
    if S >= cache_len:
        new = {n: a[:, -cache_len:] for n, a in new.items()}
        positions = positions[:, -cache_len:]
    slots = (positions % cache_len).astype(jnp.int32)
    rows = jnp.arange(B, dtype=jnp.int32) if slot_idx is None else slot_idx
    at = pool_index(layer, rows[:, None], slots)
    out = {n: cache[n].at[at].set(a.astype(cache[n].dtype))
           for n, a in new.items()}
    out["pos"] = cache["pos"].at[at].set(positions.astype(jnp.int32))
    return out


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


#: Positions per block of an absorbed decode step's read of the cache.
MLA_DECODE_CHUNK = 512


def mla_cache_width(mla) -> int:
    """Width of a cached position: the latent and the rotary key, padded to
    a whole number of the TPU's 128-lane tiles. Unpadded (512 + 64), the
    TPU lays the leaf out position-minor, and every decode step's write of
    the new entries copied the whole pool into another layout and back."""
    return -(-mla.latent_dim // 128) * 128


def init_mla_cache(batch, cache_len, mla, dtype):
    """Empty latent cache: per position ``[c, k_pe, 0...]`` (the normed
    latent, the rotated shared key, zeros to :func:`mla_cache_width`);
    pos = -1 marks an unfilled slot."""
    return {
        "latent": jnp.zeros((batch, cache_len, mla_cache_width(mla)), dtype),
        "pos": -jnp.ones((batch, cache_len), jnp.int32),
    }


def _freq_table(params, k: int) -> jax.Array:
    """A circulant projection's ``(p, q, K)`` complex frequency table, from
    its frozen ``wr``/``wi`` (int8 ones dequantized) or its time-domain
    ``w``."""
    from repro.core import circulant as circ

    if "wr" in params:
        wr, wi = circ.dequantize_freq_pair(params["wr"], params["wi"],
                                           params.get("w_scale"))
        return jax.lax.complex(wr.astype(jnp.float32),
                               wi.astype(jnp.float32))
    return jnp.fft.rfft(params["w"].astype(jnp.float32), n=k, axis=-1)


@dataclasses.dataclass(frozen=True)
class MLAttention:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1),
    no query compression::

        q = x Wq                      -> per head [q_nope (dn), q_pe (dr)]
        [c, k_pe] = x Wkv_a;  c = rms(c)
        [k_nope, v] = c Wkv_b         -> per head (dn, dv)
        q_pe, k_pe = rope(q_pe), rope(k_pe)     # one k_pe for all heads
        o = softmax([q_nope, q_pe] . [k_nope, k_pe] * scale) v;  out = o Wo

    The cache holds ``c`` and ``k_pe`` per position (``latent_dim``
    values, padded to :func:`mla_cache_width`) in place of per-head keys
    and values. A one-token call (decode) attends over it in the absorbed
    form: ``q_nope`` is taken into the latent space by ``Wuk^T`` (the
    k_nope rows of ``Wkv_b``), scored against ``[c, k_pe]``, and the
    weighted sum of latents is taken out by ``Wuv`` (the v rows) — each
    step reads one cache row a position, for all heads. A longer call
    (training, prefill) expands keys and values
    over its own positions and attends over those: a prefill launch starts
    its rows blank (``DecoderRunner.prefill``; the prefix cache, which
    seeds rows from a donor, is refused for these layers), so the cache
    holds nothing before it. With YaRN the scale is
    ``qk_head_dim ** -0.5 * mscale(factor, mscale_all_dim) ** 2``."""

    cfg: ModelConfig
    stack: Tuple[int, ...] = ()

    @property
    def dims(self):
        return self.cfg.mla

    def _lin(self, i, o, ia, oa):
        return Linear(in_dim=i, out_dim=o, in_axis=ia, out_axis=oa,
                      family="attn", swm=self.cfg.swm, stack=self.stack,
                      dtype=self.cfg.param_dtype)

    @property
    def q_proj(self):
        return self._lin(self.cfg.d_model,
                         self.cfg.n_heads * self.dims.qk_head_dim,
                         "embed", "heads")

    @property
    def kv_a_proj(self):
        return self._lin(self.cfg.d_model, self.dims.latent_dim, "embed",
                         None)

    @property
    def kv_norm(self):
        return RMSNorm(self.dims.kv_lora_rank, stack=self.stack)

    @property
    def kv_b_proj(self):
        m = self.dims
        return self._lin(m.kv_lora_rank,
                         self.cfg.n_heads * (m.qk_nope_head_dim
                                             + m.v_head_dim),
                         None, "heads")

    @property
    def o_proj(self):
        return self._lin(self.cfg.n_heads * self.dims.v_head_dim,
                         self.cfg.d_model, "heads", "embed")

    def specs(self):
        return {"q": self.q_proj.specs(), "kv_a": self.kv_a_proj.specs(),
                "kv_norm": self.kv_norm.specs(),
                "kv_b": self.kv_b_proj.specs(), "o": self.o_proj.specs()}

    @property
    def scale(self) -> float:
        s = self.dims.qk_head_dim ** -0.5
        y = self.cfg.yarn
        if y is not None and y.mscale_all_dim:
            s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
        return s

    @jax.named_scope("attention")
    def __call__(self, params, x, positions, *, cache=None, slot_idx=None,
                 layer=None):
        """As :class:`Attention`: with ``slot_idx`` the cache is the whole
        slot pool, updated in place. Returns ``(out, new_cache)``."""
        cfg, m = self.cfg, self.dims
        B, S, _ = x.shape
        H, dn, dr, r = (cfg.n_heads, m.qk_nope_head_dim, m.qk_rope_head_dim,
                        m.kv_lora_rank)
        q = self.q_proj(params["q"], x).reshape(B, S, H, dn + dr)
        ckv = self.kv_a_proj(params["kv_a"], x)
        c = self.kv_norm(params["kv_norm"], ckv[..., :r])
        cos, sin = rotary(positions, dr, cfg.rope_theta, cfg.yarn)
        q_pe = apply_rope(q[..., dn:], cos, sin)
        k_pe = apply_rope(ckv[..., None, r:], cos, sin)[:, :, 0]
        new_cache = None
        if cache is not None:
            pad = jnp.zeros((B, S, mla_cache_width(m) - m.latent_dim),
                            c.dtype)
            new_cache = write_cache(
                cache, {"latent": jnp.concatenate([c, k_pe, pad], -1)},
                positions, slot_idx, layer)
        if cache is not None and S == 1:
            out = self._absorbed(params["kv_b"], q[..., :dn], q_pe,
                                 new_cache, positions, slot_idx, layer)
        else:
            out = self._expanded(params["kv_b"], q[..., :dn], q_pe, c, k_pe,
                                 positions)
        return self.o_proj(params["o"], out.reshape(B, S, -1)), new_cache

    def _expanded(self, p_kvb, q_nope, q_pe, c, k_pe, positions):
        """Keys and values of every head over the call's own positions."""
        cfg, m = self.cfg, self.dims
        B, S, H, dn = q_nope.shape
        kv = self.kv_b_proj(p_kvb, c).reshape(B, S, H,
                                              dn + m.v_head_dim)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None],
                                            (B, S, H, k_pe.shape[-1]))], -1)
        q = jnp.concatenate([q_nope, q_pe], -1)[:, :, :, None]
        attend = flash_attention if S > cfg.flash_q_chunk else \
            _direct_attention
        kw = (dict(q_chunk=cfg.flash_q_chunk, kv_chunk=cfg.flash_kv_chunk)
              if S > cfg.flash_q_chunk else {})
        out = attend(q, k, kv[..., dn:], positions, positions, causal=True,
                     window=0, prefix_len=0, softcap=0.0, scale=self.scale,
                     **kw)
        return out[:, :, :, 0]

    def _absorbed(self, p_kvb, q_nope, q_pe, cache, q_pos, slot_idx,
                  layer):
        """One query position against the cached ``[c, k_pe]`` of rows
        ``slot_idx`` (all rows without it): ``[Wuk^T q_nope, q_pe]`` scores
        the latent and the shared key in one product, and ``Wuv`` expands
        the weighted latent. The cache is read by
        :func:`~repro.kernels.latent_attention.latent_decode_attention`:
        each row's filled blocks of :data:`MLA_DECODE_CHUNK` positions,
        once, straight from the pool."""
        m = self.dims
        r, dn, dv = m.kv_lora_rank, m.qk_nope_head_dim, m.v_head_dim
        B, _, H, _ = q_nope.shape
        qn = q_nope[:, 0].astype(jnp.float32)                 # (B, H, dn)
        kb = self.kv_b_proj
        if kb.is_circulant:
            k = kb.block_size
            if dn % k or dv % k:
                raise ValueError(
                    f"absorbed latent attention needs the kv_b block size "
                    f"{k} to divide qk_nope_head_dim {dn} and v_head_dim "
                    f"{dv}")
            w = _freq_table(p_kvb, k)                          # (p, q, K)
            w = w.reshape(H, (dn + dv) // k, r // k, w.shape[-1])
            wk, wv = w[:, : dn // k], w[:, dn // k:]

            def rfft(a):
                return jnp.fft.rfft(a.reshape(*a.shape[:-1], -1, k), axis=-1)

            def irfft(a):
                y = jnp.fft.irfft(a, n=k, axis=-1)
                return y.reshape(*y.shape[:-2], -1)

            # W^T of a circulant block multiplies by conj(FFT(w))
            q_lat = irfft(jnp.einsum("bhif,hijf->bhjf", rfft(qn),
                                     jnp.conj(wk)))
        else:
            w = p_kvb["w"].astype(jnp.float32).reshape(r, H, dn + dv)
            wk, wv = w[..., :dn], w[..., dn:]
            q_lat = jnp.einsum("bhn,rhn->bhr", qn, wk)
        dtype = cache["latent"].dtype
        W = cache["latent"].shape[-1]
        qa = jnp.concatenate(
            [q_lat, q_pe[:, 0].astype(jnp.float32),
             jnp.zeros((B, H, W - m.latent_dim), jnp.float32)], -1
        ).astype(dtype)
        T = cache["pos"].shape[-1]
        o_lat = latent_decode_attention(
            qa, cache["latent"], cache["pos"], q_pos[:, 0], slot_idx, layer,
            r=r, scale=self.scale,
            block=MLA_DECODE_CHUNK if T % MLA_DECODE_CHUNK == 0 else T)
        if kb.is_circulant:
            o = irfft(jnp.einsum("bhjf,hijf->bhif", rfft(o_lat), wv))
        else:
            o = jnp.einsum("bhr,rhv->bhv", o_lat, wv)
        return o[:, None].astype(dtype)                       # (B, 1, H, dv)
