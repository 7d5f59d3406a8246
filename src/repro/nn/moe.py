"""Mixture-of-Experts with scatter-based capacity dispatch (GShard-style).

Expert parallelism: the expert axis carries the 'experts' logical axis →
sharded over the mesh 'model' axis. Token→expert dispatch is a scatter-add
into an (E, C, d) buffer; GSPMD inserts the all-to-all when the token
sharding (batch over 'data') meets the expert sharding ('model').

Routing is a plain dense GEMM + top-k in float32 — never SWM-compressed (it
is not one of the paper's weight-matrix targets). The gates are the top-k
softmax probabilities, renormalised over the k (``norm_topk``) or scaled by
``routed_scale`` (DeepSeek-V2: ``norm_topk_prob`` false).
Expert FFN weights ARE compressed when `swm.targets` includes 'expert' —
on arctic-480b this is where the paper's O(n)-storage claim bites hardest
(128 experts × 35 layers of circulant tables instead of dense matrices).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.nn.ffn import SwiGLU
from repro.nn.linear import Linear

__all__ = ["MoE", "NO_DROP_CHUNK"]

#: Tokens per dispatch in the serving (no-drop) path: its ``(E, C, d)``
#: buffer and expert hiddens grow with the chunk, not with the launch.
NO_DROP_CHUNK = 256


@dataclasses.dataclass(frozen=True)
class MoE:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    norm_topk: bool = True
    routed_scale: float = 1.0
    swm: "SWMConfig" = None
    stack: Tuple[int, ...] = ()
    dtype: str = "bfloat16"

    @property
    def router(self):
        return Linear(
            in_dim=self.d_model, out_dim=self.n_experts,
            in_axis="embed", out_axis=None, family="router",
            swm=self.swm, stack=self.stack, dtype="float32",
        )

    @property
    def experts(self):
        return SwiGLU(
            d_model=self.d_model, d_ff=self.d_ff, swm=self.swm,
            stack=self.stack, expert_dims=(self.n_experts,),
            family="expert", dtype=self.dtype,
        )

    def specs(self):
        return {"router": self.router.specs(), "experts": self.experts.specs()}

    # ------------------------------------------------------------------
    @jax.named_scope("moe")
    def __call__(self, params, x: jax.Array, no_drop: bool = False):
        """x (B, S, d) -> (y (B, S, d), aux_loss scalar).

        ``no_drop=True`` is the serving path: every (token, slot) pair runs
        through its own expert, whose tables are gathered for the pair, so
        the work is that of the N·T routed rows and nothing is ever dropped.
        Each token's output then depends only on its own row — independent
        of batch composition and bucket padding — which is what lets the
        serve engine run MoE configs bit-identically across bucket shapes.
        The tokens go through in chunks of :data:`NO_DROP_CHUNK`, so the
        gathered tables are those of one chunk whatever the launch.
        Training keeps the capacity-factor drop path (the load-balance
        pressure the aux loss is tuned against)."""
        B, S, d = x.shape
        E, T = self.n_experts, self.top_k
        N = B * S
        xt = x.reshape(N, d)

        with jax.default_matmul_precision("highest"):            # (N, E)
            logits = self.router(params["router"], xt.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_idx = jax.lax.top_k(probs, T)                       # (N, T)
        if self.norm_topk:
            gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
        elif self.routed_scale != 1.0:
            gate = gate * self.routed_scale

        # load-balance aux loss (Switch): E · Σ_e f_e · P_e
        flat_e = expert_idx.reshape(-1)
        f = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (N * T)
        aux = E * jnp.sum(f * probs.mean(axis=0))

        if not no_drop:
            C = min(N, max(1, int(N * T / E * self.capacity_factor)))
            y = self._dispatch(params, xt, gate, expert_idx, C)
        elif N <= NO_DROP_CHUNK:
            y = self._routed(params, xt, gate, expert_idx)
        else:
            n = -(-N // NO_DROP_CHUNK)
            pad = n * NO_DROP_CHUNK - N

            def chunks(a):
                a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                return a.reshape(n, NO_DROP_CHUNK, *a.shape[1:])

            y = jax.lax.map(lambda c: self._routed(params, *c),
                            (chunks(xt), chunks(gate), chunks(expert_idx)))
            y = y.reshape(n * NO_DROP_CHUNK, d)[:N]
        return y.reshape(B, S, d), aux

    def _dispatch(self, params, xt, gate, expert_idx, C: int):
        """Tokens ``xt (N, d)`` through their top-k experts at capacity
        ``C`` per expert (a token past its expert's capacity is dropped
        there); returns the gate-weighted sum ``(N, d)``."""
        N, d = xt.shape
        E, T = self.n_experts, self.top_k
        # position of each (token, slot) within its expert's capacity —
        # sort-based, O(N·T) memory. (A cumsum over a one-hot (N·T, E)
        # tensor needs N·T·E ints: 537 GB for qwen3-moe train_4k.)
        flat_e = expert_idx.reshape(-1)                                  # (N·T,)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))            # (E,)
        pos_sorted = jnp.arange(N * T) - seg_start[sorted_e]
        pos = jnp.zeros((N * T,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32)).reshape(N, T)
        keep = (pos < C)
        pos = jnp.where(keep, pos, 0)

        # dispatch: scatter tokens into (E, C, d)
        disp = jnp.zeros((E, C, d), xt.dtype)
        contrib = xt[:, None, :] * keep[..., None].astype(xt.dtype)  # (N,T,d)
        disp = disp.at[expert_idx, pos].add(contrib)

        # expert compute — vmap the SwiGLU over the expert axis; the expert
        # hiddens (E, C, d_ff) are rematerialized in backward (arctic:
        # 128 experts × capacity × 4864 would otherwise dominate HBM)
        y_exp = jax.vmap(jax.checkpoint(self._expert))(
            params["experts"], disp)                                     # (E, C, d)

        # combine: gather each token's expert outputs
        y_tok = y_exp[expert_idx, pos]                                   # (N, T, d)
        w = (gate * keep.astype(gate.dtype))[..., None].astype(xt.dtype)
        return (y_tok * w).sum(axis=1)

    def _routed(self, params, xt, gate, expert_idx):
        """Tokens ``xt (N, d)`` each through its top-k experts, one pair at
        a time: every (token, slot) pair gathers its expert's tables and
        runs alone, so the work is the N·T pairs' (not E·N, as a dispatch
        at capacity N would do) and a token's output is its own row's.
        Returns the gate-weighted sum ``(N, d)``."""
        N, d = xt.shape
        T = self.top_k
        pairs = jax.tree.map(lambda a: a[expert_idx.reshape(-1)],
                             params["experts"])                          # (N·T, ...)
        xp = jnp.repeat(xt, T, axis=0)[:, None, :]                       # (N·T, 1, d)
        y_tok = jax.vmap(self._expert)(pairs, xp).reshape(N, T, d)
        w = gate[..., None].astype(xt.dtype)
        return (y_tok * w).sum(axis=1)

    def _expert(self, p, xe):
        """One expert's SwiGLU on ``xe (C, d)`` with its own tables ``p``."""
        return SwiGLU(
            d_model=self.d_model, d_ff=self.d_ff, swm=self.swm,
            stack=(), expert_dims=(), family="expert", dtype=self.dtype,
        )(p, xe)
