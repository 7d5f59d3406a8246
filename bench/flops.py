"""Operations and bytes the model's algorithm requires, from shapes alone.

These counts do not depend on how the program implements an operation
(``swm_impl`` in the configuration file is never read here): a faster or
slower implementation of the same mathematics needs the same operations,
so a share of the peak computed from them cannot pass 100% when an
implementation changes.

* circulant projection ``(p*k) x (q*k)``, per token: q forward and p
  inverse real length-k transforms at ``5 k log2 k`` each, plus
  ``8 p q (k/2 + 1)`` for the complex products (accumulation in the
  frequency domain; the time-domain accumulation of the paper's dataflow
  would need ``p q`` inverse transforms and is not what is required);
* dense ``m x n`` projection: ``2 m n`` per token;
* attention: ``4 H hd`` per position attended (scores and values), over
  the positions each token attends (causal, its own sequence);
* logits head: ``2 V d`` per position whose logits are needed;
* training: 3 times the forward pass; recomputation does not count.

Configuration keys are those of the published ``config.json``, with
``swm_block_size`` for the circulant block.
"""

from __future__ import annotations

import math
from typing import Dict

__all__ = ["block_size", "circulant_flops", "circulant_bytes",
           "proj_flops", "token_body_flops", "attn_flops_per_pos",
           "head_flops", "prefill_flops", "decode_flops", "train_flops"]


def block_size(k: int, m: int, n: int) -> int:
    """Largest block ``<= k`` dividing both dims (1: dense)."""
    g = math.gcd(int(m), int(n))
    k = min(max(1, int(k)), g)
    while g % k:
        k -= 1
    return k


def circulant_flops(m: int, n: int, k: int) -> int:
    """Per token, ``m`` outputs from ``n`` inputs in ``k`` blocks."""
    p, q = m // k, n // k
    fft = 5 * k * int(math.log2(k))
    return q * fft + 8 * p * q * (k // 2 + 1) + p * fft


def circulant_bytes(m: int, n: int, k: int, tokens: int,
                    act_bytes: int = 2, table_bytes: int = 4) -> int:
    """Bytes one launch must move: activations in and out, and the frozen
    frequency table (real and imaginary parts, ``k/2 + 1`` bins)."""
    p, q = m // k, n // k
    return (tokens * (m + n) * act_bytes
            + 2 * p * q * (k // 2 + 1) * table_bytes)


def proj_flops(cfg: Dict, m: int, n: int) -> int:
    k = block_size(cfg.get("swm_block_size", 1), m, n)
    return circulant_flops(m, n, k) if k > 1 else 2 * m * n


def token_body_flops(cfg: Dict) -> int:
    """Projections of one token through one layer."""
    d, dff = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return (proj_flops(cfg, hq, d) + 2 * proj_flops(cfg, hkv, d)
            + proj_flops(cfg, d, hq) + 2 * proj_flops(cfg, dff, d)
            + proj_flops(cfg, d, dff))


def attn_flops_per_pos(cfg: Dict) -> int:
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def head_flops(cfg: Dict) -> int:
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def prefill_flops(cfg: Dict, n: int) -> int:
    """A prompt of ``n`` tokens, logits at its last position only."""
    L = cfg["num_hidden_layers"]
    return (L * (n * token_body_flops(cfg)
                 + attn_flops_per_pos(cfg) * n * (n + 1) // 2)
            + head_flops(cfg))


def decode_flops(cfg: Dict, ctx: int) -> int:
    """One token that attends ``ctx`` positions (itself included)."""
    L = cfg["num_hidden_layers"]
    return (L * (token_body_flops(cfg) + attn_flops_per_pos(cfg) * ctx)
            + head_flops(cfg))


def train_flops(cfg: Dict, n: int) -> int:
    """One sequence of ``n`` tokens, loss at every position, forward and
    backward."""
    L = cfg["num_hidden_layers"]
    fwd = (L * (n * token_body_flops(cfg)
                + attn_flops_per_pos(cfg) * n * (n + 1) // 2)
           + n * head_flops(cfg))
    return 3 * fwd
