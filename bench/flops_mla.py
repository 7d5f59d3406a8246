"""Operations a latent-attention MoE decoder (DeepSeek-V2) requires per
token, from shapes alone, counted as ``flops.py`` counts them.

* projections: ``flops.proj_flops`` (circulant at the block that divides
  both dims, else dense ``2 m n``); the router is dense, ``2 d E``;
* latent attention: per token the projections ``q``, ``kv_a``, ``o``.
  Prefill expands ``kv_b`` (``r -> H (dn + dv)``) and attends in the
  expanded form, ``2 H (dn + dr + dv)`` per attended position. Decode is
  absorbed: ``Wuk^T`` and ``Wuv`` (the two halves of ``kv_b``) once per
  token, and per attended position ``2 H (r + dr)`` for the scores over
  the latent and the shared rotary key plus ``2 H r`` for the weighted
  latent;
* FFN: the leading dense layers one SwiGLU of ``intermediate_size``; the
  MoE layers the ``num_experts_per_tok`` experts a token is routed to and
  the shared experts (one SwiGLU of ``n_shared_experts`` x
  ``moe_intermediate_size``). Experts computed for tokens not routed to
  them are not required and do not count;
* logits head: ``2 V d`` per position whose logits are needed.

Configuration keys are those of the published ``config.json``, with
``swm_block_size`` for the circulant block.
"""

from __future__ import annotations

from typing import Dict

from flops import head_flops, proj_flops

__all__ = ["token_body_flops", "attn_flops_per_pos", "prefill_flops",
           "decode_flops"]


def _swiglu(cfg: Dict, width: int) -> int:
    d = cfg["hidden_size"]
    return 2 * proj_flops(cfg, width, d) + proj_flops(cfg, d, width)


def _ffn(cfg: Dict, layer: int) -> int:
    if layer < cfg["first_k_dense_replace"]:
        return _swiglu(cfg, cfg["intermediate_size"])
    w = cfg["moe_intermediate_size"]
    return (2 * cfg["hidden_size"] * cfg["n_routed_experts"]
            + cfg["num_experts_per_tok"] * _swiglu(cfg, w)
            + _swiglu(cfg, cfg["n_shared_experts"] * w))


def token_body_flops(cfg: Dict, layer: int, absorbed: bool) -> int:
    """Projections of one token through layer ``layer`` (the kv expansion
    in the form the phase uses)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    attn = (proj_flops(cfg, H * (dn + dr), d) + proj_flops(cfg, r + dr, d)
            + proj_flops(cfg, d, H * dv))
    if absorbed:
        attn += proj_flops(cfg, H * dn, r) + proj_flops(cfg, H * dv, r)
    else:
        attn += proj_flops(cfg, H * (dn + dv), r)
    return attn + _ffn(cfg, layer)


def attn_flops_per_pos(cfg: Dict, absorbed: bool) -> int:
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    if absorbed:
        return 2 * H * (r + dr) + 2 * H * r
    return 2 * H * (dn + dr + cfg["v_head_dim"])


def prefill_flops(cfg: Dict, n: int) -> int:
    """A prompt of ``n`` tokens, logits at its last position only."""
    L = cfg["num_hidden_layers"]
    body = sum(token_body_flops(cfg, i, False) for i in range(L))
    return (n * body + L * attn_flops_per_pos(cfg, False) * n * (n + 1) // 2
            + head_flops(cfg))


def decode_flops(cfg: Dict, ctx: int) -> int:
    """One token that attends ``ctx`` positions (itself included)."""
    L = cfg["num_hidden_layers"]
    body = sum(token_body_flops(cfg, i, True) for i in range(L))
    return body + L * attn_flops_per_pos(cfg, True) * ctx + head_flops(cfg)
