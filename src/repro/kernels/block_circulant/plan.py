"""Frequency-domain execution plans: precompute once, launch forever.

The paper's §5 inference dataflow computes FFT(w) ONCE and keeps it resident
in BRAM; only activations stream through the FFT→∘→IFFT pipeline. This
module is the TPU analogue. A :class:`BCPlan` precomputes, per weight, at
init / checkpoint-load time:

  * the rfft'd weights ``(wr, wi)`` — padded to the chosen tile grid,
  * the tile sizes ``(pt, qt)`` and padded block counts (plumbed into the
    launch, so the plan's geometry IS the executed geometry),
  * optionally a fused bias and epilogue activation.

(The rDFT basis matrices are k-only constants served by the lru-cached
``dft_bases(k)`` at launch; plans don't duplicate them as pytree leaves.)

``plan.apply(x)`` then contains **no fft primitive and no weight-side work**
in its jaxpr — just the pad of x and one ``pallas_call``
(``jax.make_jaxpr(plan.apply)(x)`` is checked in tests). Plan *geometry*
(tile choice + padded shapes) is cached on ``(p, q, k, dtype)`` so a model
with many same-shaped layers derives it once.

``freeze_params`` walks a (specs, params) pair and attaches ``wr`` / ``wi``
next to every circulant-tagged ``w`` leaf — the serving engine calls it once
after loading a checkpoint, and ``nn.Linear`` picks the frozen path up
automatically. It also *pre-concatenates* the known fused projection groups
(attention Q/K/V; the LSTM's 8 gate tables + gate biases) into one stacked
table per group under the reserved ``"_fused"`` key — exactly the data a
:func:`build_multi_plan` ``BCMultiPlan`` would carry — so the traced
prefill/decode steps launch the fused projection without a single
``jnp.concatenate`` over weight tables in their jaxpr.

Quantized freezing (``quantize="int8"``): the frozen tables are stored int8
with ONE symmetric f32 max-abs scale per (p, q) circulant block, shared
across the K frequency bins and the re/im pair (``quant.symmetric_scales``
— the same scheme ``dist.compress`` uses on gradients), attached as a
sibling ``w_scale`` leaf. Resident table HBM drops ~4× on top of the rfft
freeze's 2×; the Pallas kernel dequantizes on the VMEM tile
(``kernel._bc_kernel``) and the pure-XLA ``dft``/``freq`` fallbacks
dequantize at trace entry, so greedy outputs are bit-identical to running
the fp32 path on host-dequantized tables. Because scales are per-block,
quantization commutes with the fused-group concatenation — scales stack
alongside the tables block for block. Tile geometry is always derived from
the fp32 ``vmem_estimate`` so quantized and fp32 plans share identical
tiles (and therefore identical serve executables/compile budget).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.circulant import concat_biases, split_outputs
from repro.core.quant import (dequantize_symmetric, quantize_symmetric,
                              symmetric_scales)
from repro.kernels.block_circulant.kernel import (choose_blocks,
                                                 choose_blocks_dw,
                                                 vmem_estimate)
from repro.kernels.block_circulant import ops as bc_ops

__all__ = [
    "BCPlan",
    "PlanGeometry",
    "build_plan",
    "build_multi_plan",
    "plan_geometry",
    "dw_geometry",
    "geometry_cache_info",
    "clear_plan_cache",
    "freeze_params",
    "count_frozen_tables",
    "frozen_table_bytes",
    "dequantize_frozen",
    "FUSED_KEY",
    "QUANTIZE_MODES",
]

# Legal ``quantize=`` values for freeze_params/build_plan (and, transitively,
# ServeEngine / launch.serve --quantize).
QUANTIZE_MODES = ("off", "int8")

# Reserved param-tree key for a pre-concatenated multi-projection frozen
# group ({"wr", "wi"[, "bias"]}). Attached by freeze_params; consumed by the
# attention QKV / LSTM gate fused paths via ``w_freq_cat``.
FUSED_KEY = "_fused"

# Default batch hint for tile choice when the runtime batch is unknown at
# plan-build time. Tile sizes (pt, qt) depend on B only when the VMEM budget
# binds; 128 matches the kernel's max bB, so plans and the per-call path
# agree everywhere the budget is slack (bitwise-identical outputs).
_B_HINT = 128


@dataclasses.dataclass(frozen=True)
class PlanGeometry:
    """Static geometry of one (p, q, k) problem: tiles + padded shapes."""

    p: int
    q: int
    k: int
    pt: int
    qt: int
    p_pad: int
    q_pad: int

    @property
    def K(self) -> int:
        return self.k // 2 + 1

    def vmem_bytes(self, bB: int, quantized: bool = False) -> int:
        """VMEM working set; ``quantized`` reports the int8-table variant.
        Tile CHOICE always uses the fp32 estimate (geometry identity)."""
        return vmem_estimate(bB, self.pt, self.qt, self.k,
                             quantized=quantized)


@functools.lru_cache(maxsize=1024)
def plan_geometry(p: int, q: int, k: int, dtype: str = "float32",
                  b_hint: int = _B_HINT) -> PlanGeometry:
    """Cached geometry, keyed on (shape, k, dtype): chosen once per layer
    shape, shared by every plan (and every step) with that signature."""
    _, pt, qt = choose_blocks(b_hint, p, q, k)
    p_pad = p + (-p) % pt
    q_pad = q + (-q) % qt
    return PlanGeometry(p=p, q=q, k=k, pt=pt, qt=qt, p_pad=p_pad, q_pad=q_pad)


@functools.lru_cache(maxsize=1024)
def dw_geometry(p: int, q: int, k: int, dtype: str = "float32",
                b_hint: int = _B_HINT) -> PlanGeometry:
    """Cached BACKWARD geometry: tiles for the transposed-geometry weight
    adjoint (``kernel.bc_dw_pallas``), keyed like :func:`plan_geometry`.

    The dw kernel's (pt, qt) tile the output block grid and its batch tile
    is the contraction axis — chosen once per (p, q, k) signature so every
    train step with the same layer shape reuses both the tile derivation
    AND the jitted dw executable (``bc_dw_pallas`` is keyed on static tile
    sizes). The batch tile itself stays runtime-chosen
    (``kernel.choose_batch_block_dw``), mirroring the forward plan path.
    """
    _, pt, qt = choose_blocks_dw(b_hint, p, q, k)
    return PlanGeometry(p=p, q=q, k=k, pt=pt, qt=qt,
                        p_pad=p + (-p) % pt, q_pad=q + (-q) % qt)


def geometry_cache_info():
    return plan_geometry.cache_info()


def dw_geometry_cache_info():
    return dw_geometry.cache_info()


def clear_plan_cache() -> None:
    plan_geometry.cache_clear()
    dw_geometry.cache_clear()


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("wr", "wi", "bias", "scale"),
    meta_fields=("k", "p", "q", "pt", "qt", "splits", "activation",
                 "interpret"),
)
@dataclasses.dataclass(frozen=True)
class BCPlan:
    """A frozen frequency-domain execution plan for one projection (or one
    stacked multi-projection). Registered as a pytree: jit/scan/device_put
    treat (wr, wi, bias, scale) as data and the geometry as static. The rDFT
    basis matrices are NOT stored — they are k-only constants that the
    launch path materializes from the lru-cached ``dft_bases(k)``.

    Quantized plans (``build_plan(..., quantize="int8")``) store wr/wi as
    int8 and carry the per-(p, q)-block f32 ``scale``; the kernel
    dequantizes in-tile. Geometry (pt, qt, padding) is identical to the
    fp32 plan of the same (p, q, k)."""

    wr: jax.Array                      # (p_pad, q_pad, K) f32 — int8 if quant
    wi: jax.Array                      # (p_pad, q_pad, K) f32 — int8 if quant
    bias: Optional[jax.Array]          # (1, p·k) f32 or None
    k: int
    p: int                             # true (unpadded) output blocks
    q: int                             # true (unpadded) input blocks
    pt: int
    qt: int
    splits: Tuple[int, ...]            # per-projection p_i (multi-plans)
    activation: str
    interpret: bool
    scale: Optional[jax.Array] = None  # (p_pad, q_pad) f32 when quantized

    # -- derived -------------------------------------------------------
    @property
    def in_dim(self) -> int:
        return self.q * self.k

    @property
    def out_dim(self) -> int:
        return self.p * self.k

    @property
    def n_projections(self) -> int:
        return len(self.splits)

    @property
    def quantized(self) -> bool:
        return self.scale is not None

    def table_bytes(self) -> int:
        """Resident bytes of the frozen tables (+ scales when quantized)."""
        n = self.wr.nbytes + self.wi.nbytes
        if self.scale is not None:
            n += self.scale.nbytes
        return n

    def cache_key(self) -> Tuple:
        """The geometry-cache key this plan was derived from."""
        return (self.p, self.q, self.k, str(self.wr.dtype))

    def dw_tiles(self) -> Tuple[int, int]:
        """(pt, qt) tiles of the plan's weight-adjoint (dw) kernel — served
        by the lru-cached :func:`dw_geometry` over the plan's PADDED table
        shape (the frozen (wr, wi) carry the forward tile padding), so
        repeated train steps reuse the same backward tiles/executable."""
        geo = dw_geometry(int(self.wr.shape[0]), int(self.wr.shape[1]),
                          self.k)
        return (geo.pt, geo.qt)

    # -- apply ---------------------------------------------------------
    def apply(self, x: jax.Array) -> jax.Array:
        """x (..., q·k) -> (..., p·k), fused epilogue included. The traced
        computation contains no fft and no weight-side transform/pad."""
        return bc_ops.block_circulant_matmul(
            x, None, w_freq=(self.wr, self.wi), w_scale=self.scale,
            bias=self.bias, activation=self.activation, k=self.k, q=self.q,
            tiles=(self.pt, self.qt), interpret=self.interpret,
        )[..., : self.out_dim]

    __call__ = apply

    def apply_multi(self, x: jax.Array) -> Tuple[jax.Array, ...]:
        """Stacked multi-projection apply: one launch, N outputs."""
        return tuple(split_outputs(self.apply(x), self.splits, self.k))


def _pad_freq(wr, wi, geo: PlanGeometry):
    pad = ((0, geo.p_pad - wr.shape[0]), (0, geo.q_pad - wr.shape[1]), (0, 0))
    if any(a or b for a, b in pad):
        wr = jnp.pad(wr, pad)
        wi = jnp.pad(wi, pad)
    return wr, wi


def _check_quantize(quantize: str) -> None:
    if quantize not in QUANTIZE_MODES:
        raise ValueError(
            f"quantize={quantize!r}; expected one of {QUANTIZE_MODES}")


def build_plan(
    w: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    activation: str = "none",
    interpret: Optional[bool] = None,
    b_hint: int = _B_HINT,
    quantize: str = "off",
) -> BCPlan:
    """Precompute a plan from a time-domain block table w (p, q, k).

    Runs rfft(w), tile choice, and padding ONCE — call at init or after
    checkpoint load, never inside the step function. ``quantize="int8"``
    additionally quantizes the padded tables (padding blocks are all-zero,
    so they land on the scale floor and still contribute exact zeros).
    """
    _check_quantize(quantize)
    interpret = bc_ops.resolve_interpret(interpret)
    p, q, k = w.shape
    geo = plan_geometry(p, q, k, "float32", b_hint)
    wr, wi = bc_ops.freq_weights(w)
    wr, wi = _pad_freq(wr, wi, geo)
    scale = None
    if quantize == "int8":
        scale = symmetric_scales(wr, wi)
        wr = quantize_symmetric(wr, scale)
        wi = quantize_symmetric(wi, scale)
    b2d = bc_ops._as_bias2d(bias)
    return BCPlan(
        wr=wr, wi=wi, bias=b2d,
        k=k, p=p, q=q, pt=geo.pt, qt=geo.qt, splits=(p,),
        activation=activation, interpret=interpret, scale=scale,
    )


def build_multi_plan(
    ws: Sequence[jax.Array],
    *,
    biases: Optional[Sequence[Optional[jax.Array]]] = None,
    activation: str = "none",
    interpret: Optional[bool] = None,
    b_hint: int = _B_HINT,
    quantize: str = "off",
) -> BCPlan:
    """Stack N same-(q, k) projections along p into ONE plan / ONE launch.

    The C-LSTM gate fusion at plan level: 4 gate matrices (or attention
    Q/K/V) that read the same input become a single (Σp_i, q, k) table.
    ``apply_multi`` splits the fused output back per projection.
    (``quantize`` commutes with the stacking — scales are per-block.)
    """
    interpret = bc_ops.resolve_interpret(interpret)
    q, k = ws[0].shape[1], ws[0].shape[2]
    for w in ws:
        if w.shape[1:] != (q, k):
            raise ValueError(
                f"multi-plan tables must share (q, k); got "
                f"{[tuple(w.shape) for w in ws]}"
            )
    splits = tuple(int(w.shape[0]) for w in ws)
    p = sum(splits)
    w_cat = jnp.concatenate(list(ws), axis=0)
    bias_cat = concat_biases(splits, biases, k)
    plan = build_plan(w_cat, bias=bias_cat, activation=activation,
                      interpret=interpret, b_hint=b_hint, quantize=quantize)
    return dataclasses.replace(plan, splits=splits)


# ---------------------------------------------------------------------------
# Whole-param-tree freezing (serving)
# ---------------------------------------------------------------------------


def _frozen_pair(d) -> bool:
    return isinstance(d, dict) and "wr" in d and "wi" in d


def _attach_fused(out: Dict[str, Any]) -> bool:
    """Attach a pre-concatenated ``FUSED_KEY`` entry when ``out`` is one of
    the known fused projection groups. Concatenation runs EAGERLY here (at
    freeze time), so the traced fused launch reads one resident table —
    no per-trace ``jnp.concatenate`` over weights. Returns True if added.

    Groups recognized:
      * attention Q/K/V — sibling dicts ``q``/``k``/``v`` of frozen tables
        sharing (q, K): stack along the output-block (p) axis;
      * LSTM gates — ``W{g}x``/``W{g}r`` for g in i/f/c/o: each gate's x-
        and recurrent-side tables concatenate along q, the four gates stack
        along p, and the gate biases ``b{g}`` pre-concatenate alongside.

    The per-projection ``wr``/``wi`` entries are KEPT alongside the fused
    copy: cross-attention layers share the q/k/v param structure but
    cannot take the fused launch (their K/V read a different input), and
    freeze-time detection cannot tell self- from cross-attention. The
    extra footprint is the rfft tables of the fused projections only —
    small next to the KV cache, and the time-domain ``w`` is still
    dropped.

    Quantized members fuse too: per-(p, q)-block scales concatenate
    alongside the tables (p axis for the projection/gate stack, q axis for
    the LSTM x/r halves) — quantization commutes with the fusion exactly
    because scales never cross a block boundary.
    """
    if FUSED_KEY in out:
        return False

    def _cat_scales(scales, cat):
        """Fused w_scale from the members' scales: all-or-nothing."""
        if all(s is not None for s in scales):
            return cat(scales)
        if any(s is not None for s in scales):
            raise ValueError(
                "fused projection group mixes quantized and fp32 frozen "
                "tables; freeze with a single quantize mode")
        return None

    qkv = [out.get(n) for n in ("q", "k", "v")]
    if all(_frozen_pair(d) for d in qkv):
        wrs = [d["wr"] for d in qkv]
        shapes = {w.shape[:-3] + w.shape[-2:] for w in wrs}
        if all(w.ndim >= 3 for w in wrs) and len(shapes) == 1:
            fused = {
                "wr": jnp.concatenate(wrs, axis=-3),
                "wi": jnp.concatenate([d["wi"] for d in qkv], axis=-3),
            }
            sc = _cat_scales([d.get("w_scale") for d in qkv],
                             lambda ss: jnp.concatenate(ss, axis=-2))
            if sc is not None:
                fused["w_scale"] = sc
            out[FUSED_KEY] = fused
            return True
        return False
    gates = []
    for g in ("i", "f", "c", "o"):
        px, pr, b = out.get(f"W{g}x"), out.get(f"W{g}r"), out.get(f"b{g}")
        if not (_frozen_pair(px) and _frozen_pair(pr) and b is not None):
            return False
        gates.append((px, pr, b))
    x_shapes = {px["wr"].shape for px, _, _ in gates}
    r_shapes = {pr["wr"].shape for _, pr, _ in gates}
    if len(x_shapes) != 1 or len(r_shapes) != 1:
        return False
    xs, rs = x_shapes.pop(), r_shapes.pop()
    # same output blocks and same K on both sides (same k by construction:
    # the x/r tables of one gate share out_dim, and equal K + equal out_dim
    # pins k); q may differ (d_in vs d_proj)
    if len(xs) != 3 or len(rs) != 3 or xs[0] != rs[0] or xs[-1] != rs[-1]:
        return False
    fused = {
        "wr": jnp.concatenate(
            [jnp.concatenate([px["wr"], pr["wr"]], axis=-2)
             for px, pr, _ in gates], axis=-3),
        "wi": jnp.concatenate(
            [jnp.concatenate([px["wi"], pr["wi"]], axis=-2)
             for px, pr, _ in gates], axis=-3),
        "bias": jnp.concatenate(
            [b.reshape(-1).astype(jnp.float32) for _, _, b in gates]),
    }
    sc = _cat_scales(
        [s for px, pr, _ in gates
         for s in (px.get("w_scale"), pr.get("w_scale"))],
        lambda ss: jnp.concatenate(
            [jnp.concatenate(ss[2 * i: 2 * i + 2], axis=-1)
             for i in range(len(ss) // 2)], axis=-2))
    if sc is not None:
        fused["w_scale"] = sc
    out[FUSED_KEY] = fused
    return True


def freeze_params(specs, params, quantize: str = "off") -> Dict[str, Any]:
    """Replace every circulant table with its frozen frequency weights.

    Walks the ParamSpec tree (which tags circulant leaves — see
    ``nn.Linear.specs``) in lockstep with the param pytree; every tagged
    ``w`` is REPLACED by entries ``wr`` / ``wi`` = rfft(w) along the last
    axis (leading stack/expert dims preserved, so scan-over-layers slices
    them consistently). Dropping the time-domain table matters: keeping it
    would roughly double the circulant weight footprint in device memory
    for the process lifetime of a serving job. ``nn.Linear`` (and the
    fused lstm/attention/ffn paths) detect the frozen entries and take the
    no-fft path without touching ``w``.

    ``quantize="int8"`` stores the frozen tables int8 with a sibling
    ``w_scale`` leaf — one symmetric f32 max-abs scale per (p, q) block,
    shared over the K bins and the re/im pair (``quant.symmetric_scales``).
    Resident table bytes drop ~4×; dequantization happens in-kernel (Pallas
    path) or at trace entry (XLA ``dft``/``freq`` fallback), both bit-
    identical to the fp32 path on dequantized tables. An already-frozen
    fp32 tree re-frozen with ``"int8"`` quantizes in place (no new rfft);
    an already-quantized tree is passed through unchanged under either
    mode (``"off"`` never dequantizes — see :func:`dequantize_frozen`).

    Fused groups (attention Q/K/V, LSTM gates) additionally get a
    pre-concatenated stacked table under :data:`FUSED_KEY` — built here,
    eagerly, from the just-frozen per-projection tables (zero extra rfft
    work), so the fused launch needs no weight concatenation in its trace.
    Idempotent; non-circulant subtrees are returned as-is (same objects,
    no copy).
    """
    from repro.nn.module import ParamSpec

    _check_quantize(quantize)
    if isinstance(specs, ParamSpec) or not isinstance(specs, dict) \
            or not isinstance(params, dict):
        return params
    out = {}
    dropped = set()
    changed = False
    for key, sub_spec in specs.items():
        sub_param = params[key] if key in params else None
        if (isinstance(sub_spec, ParamSpec) and key == "w"
                and "circulant" in getattr(sub_spec, "tags", ())):
            if "wr" in params and "wi" in params:   # already frozen
                wr, wi = params["wr"], params["wi"]
                if (quantize == "int8" and "w_scale" not in params
                        and jnp.issubdtype(wr.dtype, jnp.floating)):
                    # fp32-frozen checkpoint re-frozen quantized: no rfft,
                    # just the int8 encode
                    sc = symmetric_scales(wr, wi)
                    out["w_scale"] = sc
                    wr = quantize_symmetric(wr, sc)
                    wi = quantize_symmetric(wi, sc)
                    changed = True
                out["wr"], out["wi"] = wr, wi
            else:
                wr, wi = bc_ops.freq_weights(sub_param)
                if "conv_taps" in sub_spec.tags:
                    # conv tap tables (r², p, q, k) freeze straight into the
                    # (p, r²·q, K) im2col block-table layout the kernel
                    # consumes, so the traced conv step does no weight-side
                    # transpose/reshape (freeze-once, like the fused groups)
                    t, p, q, K = wr.shape
                    wr = wr.transpose(1, 0, 2, 3).reshape(p, t * q, K)
                    wi = wi.transpose(1, 0, 2, 3).reshape(p, t * q, K)
                if quantize == "int8":
                    # quantize AFTER any layout reshape so the (p, q) scale
                    # grid matches the stored table's block grid
                    sc = symmetric_scales(wr, wi)
                    out["w_scale"] = sc
                    wr = quantize_symmetric(wr, sc)
                    wi = quantize_symmetric(wi, sc)
                out["wr"], out["wi"] = wr, wi
                changed = True
            if "w" in params:
                dropped.add("w")
                changed = True
        else:
            new = freeze_params(sub_spec, sub_param, quantize)
            out[key] = new
            changed = changed or (new is not sub_param)
    # preserve params-only keys (already-frozen trees stay intact)
    for key in params:
        if key in out or key in dropped:
            continue
        if (key == FUSED_KEY and quantize == "int8"
                and isinstance(params[key], dict)
                and "w_scale" not in params[key]):
            # stale fp32 fused group over members just re-quantized above:
            # drop it so _attach_fused rebuilds it from the int8 tables
            changed = True
            continue
        out[key] = params[key]
    changed = _attach_fused(out) or changed
    return out if changed else params


def frozen_table_bytes(params) -> int:
    """Resident bytes of every frozen table in a param tree: all ``wr`` /
    ``wi`` pairs (fused copies included — they are resident too) plus any
    ``w_scale`` leaves. The serve-path quantization acceptance compares
    this between an int8-frozen and an fp32-frozen tree (int8 lands at
    ~0.25× + the per-block scale overhead, comfortably under the 0.55×
    budget)."""
    if not isinstance(params, dict):
        return 0
    n = 0
    for key in ("wr", "wi", "w_scale"):
        if key in params and hasattr(params[key], "nbytes"):
            n += int(params[key].nbytes)
    return n + sum(frozen_table_bytes(v) for v in params.values()
                   if isinstance(v, dict))


def dequantize_frozen(params):
    """int8-frozen tree -> the equivalent fp32-frozen tree (oracle path).

    Wherever a ``(wr, wi, w_scale)`` triple appears, replace the tables
    with ``quant.dequantize_symmetric`` f32 pairs and drop the scale.
    Feeding the result to a ``quantize="off"`` engine reproduces the int8
    engine's outputs BIT-IDENTICALLY: the kernel's in-tile dequant computes
    the same floats this function does, and everything downstream is the
    same executable. Non-dict subtrees pass through untouched.
    """
    if not isinstance(params, dict):
        return params
    out = {}
    for key, val in params.items():
        if key == "w_scale" and "wr" in params:
            continue
        if key in ("wr", "wi") and "w_scale" in params:
            out[key] = dequantize_symmetric(val, params["w_scale"])
        else:
            out[key] = dequantize_frozen(val)
    return out


def count_frozen_tables(params) -> int:
    """Number of frozen frequency tables (``wr``/``wi`` pairs) in a param
    tree — i.e. how many rfft(w) transforms :func:`freeze_params` performed.
    The serving engine's freeze-once invariant is asserted against this
    (``ops.freq_weights_trace_count`` must grow by exactly this much at
    engine construction and not at all afterwards). ``FUSED_KEY`` entries
    are skipped: they are eager concatenations of already-frozen tables,
    not additional transforms."""
    if not isinstance(params, dict):
        return 0
    n = 1 if ("wr" in params and "wi" in params) else 0
    return n + sum(count_frozen_tables(v) for key, v in params.items()
                   if key != FUSED_KEY)
