"""ModelRunner — the protocol between :class:`ServeEngine` and a model.

The engine schedules requests, buckets launch shapes, owns the prefix
index, and snapshots host state; everything model-shaped lives behind a
runner. A runner owns the device *state tree* (KV caches, recurrent
state, cross-attention KV — whatever the family persists per slot) and
exposes exactly the operations the engine composes:

* ``init_state(batch)`` — a fresh state tree with one row per slot;
* ``prefill(params, tokens, positions, state, slot_idx, ...)`` — run a
  bucket-shaped prompt group and scatter its rows into the slot state at
  ``slot_idx``; returns ``(last_logits, ok, placed_state)``. The state is
  positional argument 3 so the engine can donate it
  (``donate_argnums=(3,)``);
* ``decode(params, tokens, state, pos, slot_idx)`` — decode one token
  for the rows named by ``slot_idx``; returns ``(logits, ok,
  new_state)``. State is positional argument 2 (``donate_argnums=(2,)``).
  Decoder runners decode in place on the donated pool, writing only each
  row's new entries; the enc-dec runner gathers the rows, decodes and
  scatters them back;
* ``gather_state`` / ``place_state`` / ``reset_rows`` — row-level state
  surgery (slot compaction, scrubbing poisoned slots, restore).

**Pad contract.** Prefill buckets are LEFT-padded: real tokens sit
rightmost, pad lanes carry negative positions. A runner must guarantee
pad lanes contribute *exactly nothing* — attention masks ``kv_pos < 0``,
recurrent mixers are handed a ``positions >= 0`` validity mask (segment
mask) so pads never enter token shifts, conv windows, or state updates.
The engine asserts nothing about how; it only relies on bucket-shape
invariance: the same request must produce bit-identical tokens at any
bucket shape, including the unbucketed B=1 loop.

**State-tree shape rules.** The state tree is an arbitrary pytree whose
leaves each carry a slot axis: axis 0 for plain decoder groups, axis 1
for repeat-stacked groups and the enc-dec layer-stacked leaves. In the
runners, ``gather_state``/``place_state``/``reset_rows`` know which axis
that is; a decoder runner's in-place decode hands the pool to the model,
whose layers index it the same way (``nn.attention.pool_index``).
Snapshot/restore never inspects the tree: it flattens leaves generically
(``serve.guard.flatten_state_tree``) and restores against
``init_state``'s structure and dtypes.

**Capability flags.** ``supports_prefix_cache`` declares whether state
rows are position-sliceable (a donor's rows for positions ``[0, m)`` can
seed another request). Full-length KV caches are; recurrent state is not
(a single state vector encodes the whole prompt — there are no
per-position rows to copy), nor are short local-attention rings (donor
rows past the window are overwritten). ``prefix_cache_unsupported_reason``
carries the actionable message the engine raises. ``min_cache_len``
bounds ``cache_len`` from below; ``max_prefill_tokens`` bounds a prefill
launch's rows x prompt bucket; ``requires_extra`` marks families whose
requests carry per-request conditioning (the enc-dec encoder frames).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

__all__ = ["ModelRunner", "DecoderRunner", "RecurrentRunner",
           "EncDecRunner", "make_runner", "recurrent_mixer_names",
           "PREFILL_ACTIVATION_BYTES", "prefill_token_bytes"]

#: Device bytes the activations of one prefill launch may take, beside the
#: weights and the slot pool: a decoder's ``max_prefill_tokens`` is this
#: over :func:`prefill_token_bytes`.
PREFILL_ACTIVATION_BYTES = 2_500_000_000


def prefill_token_bytes(cfg: ModelConfig) -> int:
    """Device bytes one token of a prefill launch holds at its peak: about
    six float32 copies of the model's widest hidden (an FFN's gate and up
    projections and their frequency transforms; 23 bytes per unit of
    width measured at DeepSeek-V2-Lite's 10,944: 3.26 GB of temporaries
    at 8 x 1024 tokens, AOT on a described v5e)."""
    return 24 * max(cfg.d_ff, cfg.d_model)


def recurrent_mixer_names(cfg: ModelConfig) -> Tuple[str, ...]:
    """Sorted unique recurrent mixer kinds ('mamba'/'rwkv') in ``cfg`` —
    empty for pure-attention decoder families."""
    if cfg.family == "encdec":
        return ()
    names = {lspec.mixer for group in cfg.layer_groups()
             for lspec in group.layers if lspec.mixer in ("mamba", "rwkv")}
    return tuple(sorted(names))


class ModelRunner:
    """Base runner: holds the model/config and declares the capability
    flags; subclasses implement the device-side protocol."""

    #: whether state rows are position-sliceable (prefix-cache donors)
    supports_prefix_cache: bool = False
    #: actionable message raised when prefix_cache=True is requested
    prefix_cache_unsupported_reason: str = ""
    #: smallest servable cache_len
    min_cache_len: int = 1
    #: most tokens (rows x prompt bucket) one prefill launch may hold; the
    #: engine launches fewer rows of a longer bucket (0: no limit)
    max_prefill_tokens: int = 0
    #: whether requests must carry per-request conditioning (Request.extra)
    requires_extra: bool = False

    def __init__(self, model, cfg: ModelConfig, cache_len: int):
        self.model = model
        self.cfg = cfg
        self.cache_len = int(cache_len)

    def specs(self):
        return self.model.specs()

    # -- device-side protocol (see module docstring) ---------------------
    def init_state(self, batch: int):
        raise NotImplementedError

    def prefill(self, params, tokens, positions, state, slot_idx,
                donor_idx=None, match_len=None, extra=None):
        raise NotImplementedError

    def decode(self, params, tokens, state, pos, slot_idx):
        raise NotImplementedError

    def gather_state(self, state, idx):
        raise NotImplementedError

    def place_state(self, state, sub, idx):
        raise NotImplementedError

    def reset_rows(self, state, idx):
        """Overwrite the rows named by ``idx`` with fresh (blank) rows."""
        blank = self.init_state(int(idx.shape[0]))
        return self.place_state(state, blank, idx)

    # -- host-side hooks -------------------------------------------------
    def prewarm_extra(self, batch: int):
        """Placeholder ``extra`` for prewarm launches (families with
        ``requires_extra``); None otherwise."""
        return None

    def validate_request(self, r) -> None:
        """Family-specific admission checks beyond the engine's shared
        length/budget contract."""
        if getattr(r, "extra", None) is not None:
            raise ValueError(
                f"request carries extra conditioning but "
                f"{type(self).__name__} serves a decoder-only family that "
                f"takes none (drop Request.extra, or serve an enc-dec "
                f"config)")


class DecoderRunner(ModelRunner):
    """Runner over :class:`HybridDecoderLM` — bit-identical to the
    pre-refactor engine device path (the refactor's oracle); decode runs
    in place on the slot pool.

    The state tree is the model's cache: a list with one dict per layer
    group; leaves carry the slot axis at 0 (plain groups) or 1
    (repeat-stacked groups, leading scan axis). ``moe_no_drop=True`` is
    passed on every forward so MoE configs dispatch without capacity
    drops (batch- and pad-invariant; see :class:`repro.nn.moe.MoE`).
    """

    def __init__(self, model, cfg: ModelConfig, cache_len: int):
        super().__init__(model, cfg, cache_len)
        self._repeat_axes = tuple(
            1 if g.repeat > 1 else 0 for g in cfg.layer_groups()
        )
        self.supports_prefix_cache = True
        self.max_prefill_tokens = (PREFILL_ACTIVATION_BYTES
                                   // prefill_token_bytes(cfg))
        from repro.models.decoder import local_attn_cache_len
        for group in cfg.layer_groups():
            for lspec in group.layers:
                if lspec.mixer == "mla":
                    self.supports_prefix_cache = False
                    self.prefix_cache_unsupported_reason = (
                        "prefix_cache seeds a prefill launch with a donor's "
                        "rows, but 'mla' layers prefill in the expanded "
                        "form over the launch's own positions only: the "
                        "copied head would not be attended")
                if lspec.mixer == "attn_local":
                    ring = local_attn_cache_len(cfg, self.cache_len)
                    if ring < self.cache_len:
                        self.supports_prefix_cache = False
                        self.prefix_cache_unsupported_reason = (
                            f"prefix_cache needs full-length KV caches, but "
                            f"'attn_local' layers keep a ring of {ring} < "
                            f"cache_len={self.cache_len} entries: donor rows "
                            f"past the window are overwritten and the shared "
                            f"head cannot be copied")

    def init_state(self, batch: int):
        return self.model.init_cache(batch, self.cache_len)

    def prefill(self, params, tokens, positions, state, slot_idx,
                donor_idx=None, match_len=None, extra=None):
        """Prefill a bucket-shaped group, then scatter its rows into the
        persistent slot state at ``slot_idx``.

        Without ``donor_idx`` the group starts from fresh (empty) rows.
        With it (the prefix-cache path), row ``j`` starts from a copy of
        slot ``donor_idx[j]``'s rows with every entry at position
        ``>= match_len[j]`` masked out — the shared prompt head is copied,
        not recomputed, and ``tokens``/``positions`` carry only the
        unmatched tail. A missing match passes the row's own slot with
        ``match_len 0`` (fully-masked seed == fresh rows, bit-identical:
        masked entries contribute exactly zero to attention).

        Returns ``(last_logits, ok, placed_state)``: ``ok[j]`` is a
        device-side per-row finiteness flag (all logits finite) — the
        error-isolation guard rides in this executable's epilogue instead
        of costing a separate compile."""
        B = tokens.shape[0]
        if donor_idx is None:
            fresh = self.init_state(B)
        else:
            fresh = self._seed_state(state, donor_idx, match_len)
        logits, filled, _ = self.model.forward(
            params, tokens, positions=positions, cache=fresh,
            logits_mode="last", moe_no_drop=True,
        )
        last = logits[:, -1]
        ok = jnp.isfinite(last).all(axis=-1)
        return last, ok, self.place_state(state, filled, slot_idx)

    def _seed_state(self, state, donor_idx, match_len):
        """Bucket-shaped state seeded from donor slot rows: entries at
        positions ``>= match_len`` (donor tail/decode rows and donor pads)
        get ``pos -> -1`` so only the matched head survives the attention
        mask. k/v values past the match are left in place — masked lanes
        contribute exactly zero, so they never reach the output."""
        sub = self.gather_state(state, donor_idx)
        out = []
        for axis, g in zip(self._repeat_axes, sub):
            m = match_len[:, None] if axis == 0 else match_len[None, :, None]

            def seed(d, m=m):
                return {
                    name: (jnp.where(leaf < m, leaf, -1)
                           if name == "pos" else leaf)
                    for name, leaf in d.items()
                }

            out.append({name: seed(layer) for name, layer in g.items()})
        return out

    def decode(self, params, tokens, state, pos, slot_idx):
        """Decode one token for the slot rows named by ``slot_idx`` in
        place on the persistent slot state: per layer only the new K/V/pos
        entries are written (recurrent state: the launched rows), and
        attention reads the rows straight from the pool — no gathered
        sub-batch, no scatter of whole rows. ``tokens (Bb, 1)``, ``pos
        (Bb,)``, ``slot_idx (Bb,)`` distinct rows; bit-identical to
        gathering the rows, decoding them and scattering them back.

        Returns ``(logits, ok, state)`` — ``ok`` is the same per-row
        finiteness flag as ``prefill`` (no extra executable)."""
        logits, state = self.model.decode_step(params, tokens, state, pos,
                                               moe_no_drop=True,
                                               slot_idx=slot_idx)
        ok = jnp.isfinite(logits).all(axis=-1)
        return logits, ok, state

    @jax.named_scope("kv_move")
    def gather_state(self, src, idx):
        """Gather slot rows into a sub-batch state (inverse of
        ``place_state``); slot axis 0 plain, 1 repeat-stacked."""
        out = []
        for axis, s_g in zip(self._repeat_axes, src):
            def take(s, axis=axis):
                return s[idx] if axis == 0 else s[:, idx]
            out.append(jax.tree.map(take, s_g))
        return out

    @jax.named_scope("kv_move")
    def place_state(self, dst, src, idx):
        """Scatter per-request state rows into slot rows. The slot axis is
        0 for plain groups and 1 for repeat-stacked groups (leading scan
        axis) — mirroring ``model.init_cache``."""
        out = []
        for axis, d_g, s_g in zip(self._repeat_axes, dst, src):
            def put(d, s, axis=axis):
                s = s.astype(d.dtype)
                return (d.at[idx].set(s) if axis == 0
                        else d.at[:, idx].set(s))
            out.append(jax.tree.map(put, d_g, s_g))
        return out


class RecurrentRunner(DecoderRunner):
    """Runner for decoder families with recurrent mixers (rwkv6, mamba,
    jamba hybrids). The device path is :class:`DecoderRunner`'s — pad
    invariance lives in the model: the ``positions >= 0`` validity mask
    computed by ``HybridDecoderLM.forward`` keeps left-pad lanes out of
    token shifts, conv windows, and state updates, so bucketed prefill is
    bit-identical to the unbucketed B=1 loop.

    Recurrent state is NOT position-sliceable: one state vector per slot
    encodes the whole prompt, so there are no per-position rows a prefix
    donor could contribute. The capability flag keeps the engine from
    indexing prompts or seeding from donors."""

    def __init__(self, model, cfg: ModelConfig, cache_len: int):
        super().__init__(model, cfg, cache_len)
        mix = recurrent_mixer_names(cfg)
        self.supports_prefix_cache = False
        self.prefix_cache_unsupported_reason = (
            f"prefix reuse copies per-position donor rows, but "
            f"{'/'.join(mix)} layers hold recurrent state with no "
            f"per-position rows to slice — a donor's state encodes its "
            f"entire prompt (serve this family with prefix_cache=False)")


class EncDecRunner(ModelRunner):
    """Runner over :class:`EncDecLM` (seamless-m4t). Requests carry the
    encoder frames as ``Request.extra`` (shape ``(enc_len, d_model)``);
    the encoder runs inside the prefill executable at admission, and the
    resulting cross-attention KV lives in the state tree alongside the
    decoder self-attention cache — decode steps read it back without ever
    re-running the encoder.

    State tree: ``{"self": ..., "cross": ...}`` with every leaf stacked
    on a leading layer axis, so the slot axis is 1 uniformly."""

    requires_extra = True

    def __init__(self, model, cfg: ModelConfig, cache_len: int):
        super().__init__(model, cfg, cache_len)
        self.enc_len = int(cfg.enc_seq or cache_len)
        self.supports_prefix_cache = False
        self.prefix_cache_unsupported_reason = (
            "enc-dec cross-attention state is computed per request from "
            "its encoder frames; donor rows cannot stand in for another "
            "request's conditioning (serve with prefix_cache=False)")

    def init_state(self, batch: int):
        return self.model.init_cache(batch, self.cache_len)

    def prefill(self, params, tokens, positions, state, slot_idx,
                donor_idx=None, match_len=None, extra=None):
        """``extra (Bb, enc_len, d_model)`` are the stacked encoder frames
        for the admitted chunk; the encoder pass runs here, once per
        request, and its cross-KV is scattered into the slot state with
        the rest of the rows."""
        B = tokens.shape[0]
        fresh = self.init_state(B)
        logits, filled, _ = self.model.forward(
            params, extra, tokens, cache=fresh, logits_mode="last",
            positions=positions,
        )
        last = logits[:, -1]
        ok = jnp.isfinite(last).all(axis=-1)
        return last, ok, self.place_state(state, filled, slot_idx)

    def decode(self, params, tokens, state, pos, slot_idx):
        sub = self.gather_state(state, slot_idx)
        logits, new_sub = self.model.decode_step(params, tokens, sub, pos)
        ok = jnp.isfinite(logits).all(axis=-1)
        return logits, ok, self.place_state(state, new_sub, slot_idx)

    @jax.named_scope("kv_move")
    def gather_state(self, state, idx):
        return jax.tree.map(lambda s: s[:, idx], state)

    @jax.named_scope("kv_move")
    def place_state(self, dst, src, idx):
        return jax.tree.map(
            lambda d, s: d.at[:, idx].set(s.astype(d.dtype)), dst, src)

    def prewarm_extra(self, batch: int):
        """Zero frames: prewarm launches run the encoder on silence —
        well-defined, finite, and scattered onto rows that the next real
        admission overwrites."""
        return jnp.zeros((batch, self.enc_len, self.cfg.d_model),
                         jnp.float32)

    def validate_request(self, r) -> None:
        extra = getattr(r, "extra", None)
        if extra is None:
            raise ValueError(
                f"enc-dec serving needs encoder frames per request: set "
                f"Request.extra to an ({self.enc_len}, {self.cfg.d_model}) "
                f"array of frame embeddings")
        a = np.asarray(extra)
        if a.shape != (self.enc_len, self.cfg.d_model):
            raise ValueError(
                f"Request.extra has shape {a.shape}, expected "
                f"({self.enc_len}, {self.cfg.d_model}) "
                f"(enc_seq x d_model for this config)")


def make_runner(model, cfg: ModelConfig, cache_len: int) -> ModelRunner:
    """Pick the runner for a config: enc-dec family -> EncDecRunner,
    recurrent mixers present -> RecurrentRunner, else DecoderRunner."""
    if cfg.family == "encdec":
        return EncDecRunner(model, cfg, cache_len)
    if recurrent_mixer_names(cfg):
        return RecurrentRunner(model, cfg, cache_len)
    return DecoderRunner(model, cfg, cache_len)
