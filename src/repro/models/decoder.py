"""HybridDecoderLM — the decoder-only backbone for the LM-family archs.

One model class covers: dense transformers (qwen3, deepseek, internlm2),
local:global interleave (gemma3), MoE (arctic — parallel dense residual;
qwen3-moe), prefix-LM VLM decoding (paligemma), Mamba+attention hybrids with
alternating MoE (jamba), and attention-free RWKV-6.

Layer structure is declared as repeated **layer groups** (configs/base.py):
params for a group are stacked on a leading ``repeat`` axis and executed via
``lax.scan`` (HLO size O(1) in depth — required to keep 94-layer dry-run
compiles tractable), with remat per scan body. Heterogeneous patterns
(gemma3's 5:1, jamba's 1:7+MoE-every-2) scan over the *pattern period*
with the distinct layers unrolled inside the body.

Caches mirror the group structure: a list (one entry per group) of dicts
keyed ``l{i}`` with a leading repeat axis, scanned as xs/ys alongside params.
Decode in place (``decode_step(..., slot_idx=...)``) takes the serving
engine's whole slot pool instead: it rides the layer scan as the carry,
indexed by the scan counter, and each step writes only its new entries.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerGroup, LayerSpec, ModelConfig
from repro.nn.attention import (Attention, MLAttention, init_kv_cache,
                                init_mla_cache, pool_index, pool_rows)
from repro.nn.ffn import SwiGLU
from repro.nn.layers import Embedding, RMSNorm
from repro.nn.moe import MoE
from repro.nn.module import ParamSpec
from repro.nn.rwkv import RWKV6ChannelMix, RWKV6TimeMix, init_rwkv_cache
from repro.nn.ssm import Mamba, init_mamba_cache

__all__ = ["HybridDecoderLM", "local_attn_cache_len"]


def local_attn_cache_len(cfg: ModelConfig, cache_len: int) -> int:
    """Ring length an ``attn_local`` layer's KV cache is allocated with.

    Single source of truth shared by cache allocation (``_layer_cache``)
    and the serve engine's prefix-cache guard (a ring shorter than
    ``cache_len`` overwrites donor rows past the window, so prefix reuse
    must refuse those configs)."""
    w = cfg.sliding_window or cache_len
    return min(w, cache_len)


@jax.named_scope("kv_move")
def _take_rows(pool, slot_idx, layer):
    """Pool rows ``slot_idx`` of one layer's state as a sub-batch."""
    return jax.tree.map(lambda a: pool_rows(a, layer, slot_idx), pool)


@jax.named_scope("kv_move")
def _put_rows(pool, rows, slot_idx, layer):
    """Scatter a sub-batch of one layer's state back to pool rows."""
    return jax.tree.map(
        lambda a, r: a.at[pool_index(layer, slot_idx)].set(r.astype(a.dtype)),
        pool, rows)


@dataclasses.dataclass(frozen=True)
class HybridDecoderLM:
    cfg: ModelConfig

    # ------------------------------------------------------------------
    # layer construction
    # ------------------------------------------------------------------
    def _mixer(self, spec: LayerSpec, stack):
        cfg = self.cfg
        if spec.mixer == "attn":
            return Attention(cfg, local=False, stack=stack,
                             prefix_len=cfg.n_img_tokens)
        if spec.mixer == "attn_local":
            return Attention(cfg, local=True, stack=stack,
                             prefix_len=cfg.n_img_tokens)
        if spec.mixer == "mla":
            return MLAttention(cfg, stack=stack)
        if spec.mixer == "mamba":
            return Mamba(cfg, stack=stack)
        if spec.mixer == "rwkv":
            return RWKV6TimeMix(cfg, stack=stack)
        raise ValueError(spec.mixer)

    def _ffn(self, spec: LayerSpec, stack):
        cfg = self.cfg
        out = {}
        if spec.mixer == "rwkv":
            out["dense"] = RWKV6ChannelMix(cfg, stack=stack)
            return out
        if spec.ffn in ("dense", "dense+moe"):
            d_ff = (cfg.d_ff_shared or cfg.d_ff) if spec.ffn == "dense+moe" \
                else cfg.d_ff
            out["dense"] = SwiGLU(d_model=cfg.d_model, d_ff=d_ff,
                                  swm=cfg.swm, stack=stack,
                                  dtype=cfg.param_dtype)
        if spec.ffn in ("moe", "dense+moe"):
            out["moe"] = MoE(d_model=cfg.d_model,
                             d_ff=cfg.d_ff_expert or cfg.d_ff,
                             n_experts=cfg.n_experts,
                             top_k=cfg.n_experts_per_token,
                             capacity_factor=cfg.capacity_factor,
                             norm_topk=cfg.moe_norm_topk,
                             routed_scale=cfg.moe_routed_scale,
                             swm=cfg.swm, stack=stack, dtype=cfg.param_dtype)
        return out

    def _layer_specs(self, spec: LayerSpec, stack):
        cfg = self.cfg
        s: Dict[str, Any] = {
            "ln1": RMSNorm(cfg.d_model, stack=stack).specs(),
            "mixer": self._mixer(spec, stack).specs(),
            "ln2": RMSNorm(cfg.d_model, stack=stack).specs(),
        }
        for name, mod in self._ffn(spec, stack).items():
            s[f"ffn_{name}"] = mod.specs()
        return s

    def specs(self):
        cfg = self.cfg
        s: Dict[str, Any] = {
            "embed": Embedding(cfg.vocab, cfg.d_model,
                               dtype=cfg.param_dtype).specs(),
            "final_norm": RMSNorm(cfg.d_model).specs(),
        }
        if not cfg.tie_embeddings:
            s["lm_head"] = {
                "w": ParamSpec((cfg.d_model, cfg.vocab),
                               jnp.dtype(cfg.param_dtype),
                               ("embed", "vocab"), init="normal",
                               scale=cfg.d_model**-0.5)
            }
        for gi, group in enumerate(cfg.layer_groups()):
            stack = (group.repeat,) if group.repeat > 1 else ()
            s[f"group{gi}"] = {
                f"l{li}": self._layer_specs(lspec, stack)
                for li, lspec in enumerate(group.layers)
            }
        return s

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int) -> List[dict]:
        """One dict per group: {l{i}: percache (repeat-stacked)}."""
        cfg = self.cfg
        caches = []
        for group in cfg.layer_groups():
            g = {}
            for li, lspec in enumerate(group.layers):
                c = self._layer_cache(lspec, batch, cache_len)
                if group.repeat > 1:
                    c = jax.tree.map(
                        lambda a: jnp.broadcast_to(
                            a, (group.repeat,) + a.shape
                        ).copy(),
                        c,
                    )
                g[f"l{li}"] = c
            caches.append(g)
        return caches

    def _layer_cache(self, lspec: LayerSpec, batch, cache_len):
        cfg = self.cfg
        if lspec.mixer == "attn":
            return init_kv_cache(batch, cache_len, cfg.n_kv_heads,
                                 cfg.head_dim, cfg.dtype)
        if lspec.mixer == "attn_local":
            return init_kv_cache(batch, local_attn_cache_len(cfg, cache_len),
                                 cfg.n_kv_heads, cfg.head_dim, cfg.dtype)
        if lspec.mixer == "mla":
            return init_mla_cache(batch, cache_len, cfg.mla, cfg.dtype)
        if lspec.mixer == "mamba":
            m = Mamba(cfg)
            return init_mamba_cache(batch, m.d_inner, cfg.mamba_d_state,
                                    cfg.mamba_d_conv, cfg.dtype)
        if lspec.mixer == "rwkv":
            return init_rwkv_cache(batch, cfg.d_model,
                                   cfg.d_model // cfg.rwkv_head_dim,
                                   cfg.rwkv_head_dim, cfg.dtype)
        raise ValueError(lspec.mixer)

    # ------------------------------------------------------------------
    # one layer
    # ------------------------------------------------------------------
    def _apply_layer(self, lspec: LayerSpec, stack, p, x, positions, cache,
                     mask=None, moe_no_drop=False, slot_idx=None, layer=None):
        """One layer. With ``slot_idx``, ``cache`` is this layer's slice of
        the slot pool (behind the repeat axis at ``layer``): attention
        writes and reads it in place; recurrent state, small and rewritten
        whole every step, is taken at ``slot_idx``, stepped and put back."""
        cfg = self.cfg
        attn = lspec.mixer in ("attn", "attn_local", "mla")
        if slot_idx is not None and not attn:
            rows = _take_rows(cache, slot_idx, layer)
            x, rows, aux = self._apply_layer(lspec, stack, p, x, positions,
                                             rows, mask=mask,
                                             moe_no_drop=moe_no_drop)
            return x, _put_rows(cache, rows, slot_idx, layer), aux
        ln1 = RMSNorm(cfg.d_model, stack=stack)
        ln2 = RMSNorm(cfg.d_model, stack=stack)
        aux = jnp.zeros((), jnp.float32)

        h = ln1(p["ln1"], x)
        mixer = self._mixer(lspec, stack)
        if attn:
            # attention masks pads through negative positions already; the
            # validity mask is only threaded to the recurrent mixers so
            # attention-family jaxprs are unchanged
            mo, new_cache = mixer(p["mixer"], h, positions, cache=cache,
                                  slot_idx=slot_idx, layer=layer)
        elif mask is not None:
            mo, new_cache = mixer(p["mixer"], h, cache=cache, mask=mask)
        else:
            mo, new_cache = mixer(p["mixer"], h, cache=cache)
        x = x + mo

        h = ln2(p["ln2"], x)
        ffns = self._ffn(lspec, stack)
        out = jnp.zeros_like(x)
        ffn_cache = None
        if "dense" in ffns:
            if lspec.mixer == "rwkv":
                if mask is not None:
                    fo, ffn_cache = ffns["dense"](p["ffn_dense"], h,
                                                  cache=cache, mask=mask)
                else:
                    fo, ffn_cache = ffns["dense"](p["ffn_dense"], h,
                                                  cache=cache)
            else:
                fo = ffns["dense"](p["ffn_dense"], h)
            out = out + fo
        if "moe" in ffns:
            fo, a = ffns["moe"](p["ffn_moe"], h, no_drop=moe_no_drop)
            out = out + fo
            aux = aux + a
        x = x + out
        if ffn_cache is not None and new_cache is not None:
            new_cache = {**new_cache, **ffn_cache}
        return x, new_cache, aux

    # ------------------------------------------------------------------
    # group execution (scan over repeats)
    # ------------------------------------------------------------------
    def _apply_group(self, gi, group: LayerGroup, params_g, x, positions,
                     cache_g, mask=None, moe_no_drop=False, slot_idx=None):
        """The group's layers over ``x``. A cache rides the layer scan as
        xs/ys; with ``slot_idx`` the cache is the slot pool, which rides as
        the carry instead (indexed by the scan counter, so each layer
        writes its new entries in place and nothing else is copied)."""
        cfg = self.cfg
        stack = (group.repeat,) if group.repeat > 1 else ()
        use_cache = cache_g is not None
        in_place = slot_idx is not None

        # Remat at LAYER granularity: a multi-layer group body (gemma3's
        # 6-layer 5:1 pattern, jamba's 8-layer period) must not require all
        # of its layers' intermediates live at once in the backward pass —
        # measured 310 GB/dev on gemma3 train_4k with body-level remat only.
        # ``mask`` rides as a traced arg (None is an empty pytree);
        # ``moe_no_drop`` is a static Python bool closed over, never traced.
        def one_layer(lspec, p_li, x, positions, mask, c, slot_idx, layer):
            return self._apply_layer(lspec, (), p_li, x, positions, c,
                                     mask=mask, moe_no_drop=moe_no_drop,
                                     slot_idx=slot_idx, layer=layer)

        layer_fn = (jax.checkpoint(one_layer, static_argnums=(0,))
                    if cfg.remat != "none" else one_layer)

        def body(carry, xs):
            x, aux, pool = carry
            p_slice, c_slice, layer = xs
            new_c = {}
            for li, lspec in enumerate(group.layers):
                key = f"l{li}"
                c = (pool if in_place else c_slice)[key] if use_cache else None
                x, nc, a = layer_fn(
                    lspec, p_slice[key], x, positions, mask, c, slot_idx,
                    layer)
                if use_cache:
                    new_c[key] = nc
                aux = aux + a
            if in_place:
                return (x, aux, new_c), None
            return (x, aux, None), (new_c if use_cache else None)

        # layer_fn already remats each layer; the scan saves only the
        # inter-layer residual stream per step (checkpointing the body as
        # well would triple forward work for no memory win).
        aux0 = jnp.zeros((), jnp.float32)
        carry = (x, aux0, cache_g if in_place else None)
        xs_cache = cache_g if use_cache and not in_place else None
        if group.repeat == 1:
            (x, aux, pool), new_cache = body(carry,
                                             (params_g, xs_cache, None))
        else:
            layers = (jnp.arange(group.repeat, dtype=jnp.int32)
                      if in_place else None)
            (x, aux, pool), new_cache = jax.lax.scan(
                body, carry, (params_g, xs_cache, layers))
        return x, (pool if in_place else new_cache), aux

    # ------------------------------------------------------------------
    # public entry points
    # ------------------------------------------------------------------
    def forward(
        self,
        params,
        tokens: jax.Array,                        # (B, S)
        *,
        positions: Optional[jax.Array] = None,
        img_embeds: Optional[jax.Array] = None,   # VLM prefix (B, P, D)
        cache: Optional[List[dict]] = None,
        logits_mode: str = "all",                 # all | last | none
        moe_no_drop: bool = False,
        slot_idx: Optional[jax.Array] = None,
    ):
        """Training / prefill forward. Returns (logits, new_cache, aux).

        ``logits_mode='none'`` returns the final *hidden* states instead of
        logits (training computes the loss chunked over the vocab);
        ``'last'`` projects only the final position (prefill) — the full
        (B, S, V) tensor is never materialized for large-vocab configs.

        When ``positions`` is given and the config has recurrent mixers
        (mamba/rwkv), a validity mask ``positions >= 0`` is threaded to
        them: the serve engine's left-pad lanes carry negative positions,
        and the mask makes them contribute exactly nothing to recurrent
        state (attention already masks pads via negative positions, so
        attention-family traces are unchanged). ``moe_no_drop=True`` is the
        serving MoE dispatch (see :class:`repro.nn.moe.MoE`). ``slot_idx``
        makes ``cache`` the whole slot pool, updated in place (see
        :meth:`decode_step`).
        """
        cfg = self.cfg
        emb = Embedding(cfg.vocab, cfg.d_model, dtype=cfg.param_dtype)
        x = emb.encode(params["embed"], tokens)
        if img_embeds is not None:
            x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
        from repro.dist.sharding import constrain_batch_leading
        x = constrain_batch_leading(x)
        B, S, _ = x.shape
        mask = None
        if positions is not None and self._has_recurrent():
            mask = positions >= 0
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        aux = jnp.zeros((), jnp.float32)
        new_caches = []
        for gi, group in enumerate(cfg.layer_groups()):
            cg = cache[gi] if cache is not None else None
            x, nc, a = self._apply_group(
                gi, group, params[f"group{gi}"], x, positions, cg,
                mask=mask, moe_no_drop=moe_no_drop, slot_idx=slot_idx,
            )
            new_caches.append(nc)
            aux = aux + a

        x = RMSNorm(cfg.d_model)(params["final_norm"], x)
        if logits_mode == "none":
            out = x
        elif logits_mode == "last":
            out = self._logits(params, x[:, -1:])
        else:
            out = self._logits(params, x)
        return out, (new_caches if cache is not None else None), aux

    def forward_hidden(self, params, tokens, *, img_embeds=None):
        """Final hidden states for chunked-loss training."""
        h, _, aux = self.forward(
            params, tokens, img_embeds=img_embeds, logits_mode="none"
        )
        return h, aux

    def output_table(self, params) -> jax.Array:
        """(V, D) matrix used by the chunked CE (tied or untied head)."""
        if self.cfg.tie_embeddings:
            return params["embed"]["table"]
        return params["lm_head"]["w"].T

    @jax.named_scope("head")
    def _logits(self, params, x):
        cfg = self.cfg
        emb = Embedding(cfg.vocab, cfg.d_model, dtype=cfg.param_dtype)
        if cfg.tie_embeddings:
            return emb.decode(params["embed"], x)
        return jnp.einsum(
            "...d,dv->...v", x.astype(jnp.float32),
            params["lm_head"]["w"].astype(jnp.float32),
        )

    def _has_recurrent(self) -> bool:
        """True when any layer carries recurrent (mamba/rwkv) state."""
        return any(l.mixer in ("mamba", "rwkv")
                   for g in self.cfg.layer_groups() for l in g.layers)

    def decode_step(
        self,
        params,
        tokens: jax.Array,       # (B, 1)
        cache: List[dict],
        pos: jax.Array,          # (B,) current absolute position
        moe_no_drop: bool = False,
        slot_idx: Optional[jax.Array] = None,   # (B,) pool rows
    ):
        """One-token decode against the cache. Returns (logits, cache).

        Without ``slot_idx`` the cache holds exactly the ``B`` rows being
        decoded. With it, ``cache`` is the whole slot pool (any number of
        rows) and batch row ``b`` decodes pool row ``slot_idx[b]`` in
        place: per layer, attention writes the ``B`` new entries and reads
        the rows straight from the pool, recurrent state is taken and put
        back per row. ``slot_idx`` must hold distinct rows. The returned
        pool equals gathering the rows, decoding them without
        ``slot_idx`` and scattering them back, bit for bit."""
        positions = pos[:, None].astype(jnp.int32)
        logits, new_cache, _ = self.forward(
            params, tokens, positions=positions, cache=cache,
            moe_no_drop=moe_no_drop, slot_idx=slot_idx,
        )
        return logits[:, -1], new_cache

    def prefill(self, params, tokens, cache, img_embeds=None):
        logits, new_cache, aux = self.forward(
            params, tokens, cache=cache, img_embeds=img_embeds,
            logits_mode="last",
        )
        return logits[:, -1], new_cache
