"""Public ops: differentiable block-circulant matmuls backed by the Pallas kernel.

``block_circulant_matmul(x, w)``: x (..., q·k) × blocks w (p, q, k) -> (..., p·k)

* forward  — Pallas kernel (frequency-domain fused; interpret mode only on
  the CPU backend, see :func:`resolve_interpret`),
  with an optional **fused epilogue** (bias add + activation) executed inside
  the kernel's final-q writeback, and an optional **frozen frequency-weight
  path** (``w_freq=(wr, wi)``) that skips the per-call ``rfft(w)`` entirely —
  the paper's BRAM-resident FFT(w) inference fast path. Execution plans
  (:mod:`.plan`) build on the frozen path.
* backward — closed-form circulant adjoints (no dense expansion), BOTH
  running as Pallas kernel launches:
    dL/dx  = g @ W : **reuses the forward kernel** with the conjugated /
             index-reversed frequency weights (a circulant transpose is the
             index-reversed vector ⇒ conj(ŵ); the block table transposes
             p ↔ q).
    dL/dw[i,j] = Σ_b x_j ⋆ g_i  (circular cross-correlation)
               = irfft( Σ_b conj(x̂_j) ∘ ĝ_i )
             : the **transposed-geometry kernel** ``kernel.bc_dw_pallas`` —
             the same per-bin complex GEMM with the train batch promoted to
             the contraction axis, accumulated in VMEM scratch. The per-bin
             (B, P, f) × (B, Q, f) outer products the einsum fallback
             materialized never touch HBM; ``plan.dw_geometry`` caches the
             backward tiles per (p, q, k) so train steps reuse executables.
             (``_dw_freq_cotangents`` below is kept as the pure-XLA einsum
             ORACLE the gradcheck suite pins the kernel against.)
  Both adjoints are O(n log n) — the paper's training-phase complexity claim
  now holds end to end, in the frozen-frequency `_freq_bwd` path too.
  Residuals carry the forward's (wr, wi) so the backward never re-rffts the
  weight table. Under ``jax.grad`` the forward runs with the activation
  *unfused* (the pre-activation is the residual), keeping
  recompute-under-grad semantics; the primal-only (inference) call is fully
  fused.

``block_circulant_matmul_multi`` stacks several projections that share one
input (LSTM gates, attention QKV) along the p axis and runs them as ONE
kernel launch (C-LSTM's fused gate dataflow).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.circulant import (concat_biases, dft_bases,
                                  dft_bases_adjoint, split_outputs)
from repro.kernels.block_circulant.kernel import (apply_activation,
                                                  bc_dw_pallas,
                                                  bc_matmul_pallas,
                                                  choose_batch_block,
                                                  choose_batch_block_dw,
                                                  choose_blocks)

__all__ = [
    "block_circulant_matmul",
    "block_circulant_matmul_multi",
    "freq_weights",
    "freq_weights_trace_count",
    "outer_dot_shapes",
    "count_pallas_launches",
    "resolve_interpret",
]


# ---------------------------------------------------------------------------
# Structural jaxpr probes (shared by the test suite and kernel_bench): the
# "no dense (P, Q) einsum in the train step" acceptance checks inspect
# traced programs, not numerics. Both are thin wrappers over the recursive
# walker in ``repro.analysis.walker`` — one traversal, shared with the
# contract auditor, that also descends while/cond/dict-valued sub-jaxprs
# the old per-probe loops missed.
# ---------------------------------------------------------------------------


def outer_dot_shapes(jaxpr) -> List[Tuple[int, ...]]:
    """Output shapes of every ``dot_general`` OUTSIDE pallas_call kernels.

    Recurses through pjit/scan/while/cond/custom-vjp sub-jaxprs but never
    into a ``pallas_call`` body — contractions inside the kernel are tiled
    VMEM work, not the dense XLA fallback. The kernel-backed-adjoint
    regressions assert that none of the returned shapes spans a circulant
    layer's (P, Q) block grid (the signature of the einsum weight adjoint).
    """
    from repro.analysis.walker import iter_eqns

    return [tuple(v.aval.shape)
            for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name == "dot_general"
            for v in eqn.outvars]


def count_pallas_launches(jaxpr) -> int:
    """Number of ``pallas_call`` eqns anywhere in the (closed) jaxpr — one
    kernel launch per execution of the enclosing region."""
    from repro.analysis.walker import iter_eqns

    return sum(1 for eqn in iter_eqns(jaxpr)
               if eqn.primitive.name == "pallas_call")


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode, decided by the default backend alone.

    ``None`` means interpret exactly when the default backend is the CPU.
    On any other backend the kernel is compiled: asking for interpret mode
    there is an error, never a silent slow path. Tests that compile for a
    described TPU from the CPU pass ``interpret=False`` explicitly.
    """
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"interpret=True on the {jax.default_backend()!r} backend: the "
            "Pallas interpreter runs only on the CPU backend")
    return bool(interpret)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# Counts every rfft(w) issued (eagerly or into a trace). Serving freezes
# weights exactly once, so the regression tests assert this counter does not
# move across an entire engine lifetime after freeze_params.
_FREQ_WEIGHT_TRACES = 0


def freq_weights_trace_count() -> int:
    """Process-wide count of ``freq_weights`` invocations (rfft(w) work)."""
    return _FREQ_WEIGHT_TRACES


def freq_weights(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Time-domain block table (..., p, q, k) -> (wr, wi) real/imag rfft.

    The frozen-inference precompute (paper: FFT(w) stored in BRAM once).
    Leading stack/expert dims pass through untouched.
    """
    global _FREQ_WEIGHT_TRACES
    _FREQ_WEIGHT_TRACES += 1
    wf = jnp.fft.rfft(w.astype(jnp.float32), axis=-1)
    return jnp.real(wf), jnp.imag(wf)


@functools.lru_cache(maxsize=512)
def _tiles(B: int, p: int, q: int, k: int) -> Tuple[int, int, int]:
    return choose_blocks(B, p, q, k)


def _run_kernel(x2d: jax.Array, wr: jax.Array, wi: jax.Array,
                bias2d: Optional[jax.Array], k: int, activation: str,
                interpret: bool,
                tiles: Optional[Tuple[int, int]] = None,
                w_scale: Optional[jax.Array] = None) -> jax.Array:
    """Pad (rows + block dims) and launch. wr/wi (P, Q, K) may already be
    tile-aligned (plan path) — padding is then a no-op. Returns the FULL
    (B, P_pad·k) output; the caller slices. ``tiles=(pt, qt)`` uses the
    plan's frozen block tiles (only the batch tile stays runtime-chosen).
    ``w_scale`` (P, Q) f32 marks wr/wi as int8 tables dequantized in-kernel
    (padding blocks carry the scale floor and all-zero int8 payloads, so
    they still contribute exact zeros)."""
    P, Q, _ = wr.shape
    B = x2d.shape[0]
    if tiles is not None:
        pt, qt = tiles
        bB = choose_batch_block(B, pt, qt, k)
    else:
        bB, pt, qt = _tiles(B, P, Q, k)
    xp = _pad_to(x2d, 0, bB)
    xp = _pad_to(xp, 1, Q * k)           # x cols up to the weight's Q blocks
    wr = _pad_to(_pad_to(wr, 0, pt), 1, qt)
    wi = _pad_to(_pad_to(wi, 0, pt), 1, qt)
    if w_scale is not None:
        w_scale = _pad_to(_pad_to(w_scale, 0, pt), 1, qt)[..., None]
    if wr.shape[1] != Q:                 # q padded -> pad x block dim to match
        xp = _pad_to(
            xp.reshape(xp.shape[0], Q, k), 1, qt
        ).reshape(xp.shape[0], -1)
    if bias2d is not None:
        bias2d = _pad_to(bias2d, 1, pt * k)
    c, s, ci, si = dft_bases(k, jnp.float32)
    y = bc_matmul_pallas(
        xp, wr, wi, c, s, ci, si, bias2d, w_scale,
        k=k, block_b=bB, block_p=pt, block_q=qt, interpret=interpret,
        activation=activation,
    )
    return y[:B]


def _transpose_freq(wr: jax.Array, wi: jax.Array):
    """Frequency weights of the transposed block-circulant matrix.

    (W^T)_{ji} = W_ij^T and a circulant transpose is the index-reversed
    vector, i.e. conj(ŵ) in the frequency domain: swap (p, q), negate wi.
    """
    return jnp.transpose(wr, (1, 0, 2)), -jnp.transpose(wi, (1, 0, 2))


def _dx_via_kernel(gz: jax.Array, wr: jax.Array, wi: jax.Array, k: int,
                   q_out: int, interpret: bool) -> jax.Array:
    """dx = gz @ W through the kernel with conj/index-reversed freq weights."""
    P = wr.shape[0]
    gzp = _pad_to(gz, 1, P * k)
    wrT, wiT = _transpose_freq(wr, wi)
    dx = _run_kernel(gzp, wrT, wiT, None, k, "none", interpret)
    return dx[:, : q_out * k]


def _dw_via_kernel(x2d: jax.Array, gz: jax.Array, P: int, Q: int, k: int,
                   interpret: bool, freq_out: bool = False):
    """Weight adjoint through the transposed-geometry Pallas kernel.

    x2d (B, ≤Q·k) and gz (B, ≤P·k) zero-pad up to the (P, Q) block grid and
    its backward tile multiples (``plan.dw_geometry``, cached per shape);
    padded rows/cols contribute exact zeros, so slicing back is lossless.
    Returns time-domain ``dw (P, Q, k)`` f32 when ``freq_out=False`` (the
    `_bwd` path) or the frequency-cotangent pair ``(dwr, dwi)`` each
    (P, Q, K) f32 when ``freq_out=True`` (the `_freq_bwd` path).
    """
    # function-level import: plan.py imports this module at load time
    from repro.kernels.block_circulant.plan import dw_geometry

    geo = dw_geometry(P, Q, k)
    bB = choose_batch_block_dw(x2d.shape[0], geo.pt, geo.qt, k)
    f32 = jnp.float32
    x = _pad_to(x2d.astype(f32), 0, bB)
    g = _pad_to(gz.astype(f32), 0, bB)
    x = jnp.pad(x, ((0, 0), (0, geo.q_pad * k - x.shape[1])))
    g = jnp.pad(g, ((0, 0), (0, geo.p_pad * k - g.shape[1])))
    C, S, CiT, SiT, CT, ST = dft_bases_adjoint(k, f32)
    out = bc_dw_pallas(x, g, C, S, CiT, SiT, CT, ST, k=k, block_b=bB,
                       block_p=geo.pt, block_q=geo.qt, freq_out=freq_out,
                       interpret=interpret)
    if freq_out:
        dwr, dwi = out
        return dwr[:P, :Q], dwi[:P, :Q]
    return out[:P, : Q * k].reshape(P, Q, k)


def _dw_freq_cotangents(x2d, gz, P, Q, k):
    """(dwr, dwi) frequency cotangents of the per-bin complex GEMM — the
    pure-XLA einsum ORACLE for :func:`_dw_via_kernel` (test/gradcheck use
    only; the hot adjoints run the transposed-geometry kernel).

    x2d (B, ≤Q·k) and gz (B, ≤P·k) are zero-padded up to the full (P, Q)
    block grid; padded rows/cols contribute exact zeros.
    """
    C, S, Ci, Si = dft_bases(k, jnp.float32)
    f32 = jnp.float32
    xb = _pad_to(x2d.astype(f32), 1, Q * k).reshape(-1, Q, k)
    xr = xb @ C
    xi = xb @ S
    gb = _pad_to(gz.astype(f32), 1, P * k).reshape(-1, P, k)
    # adjoint of the inverse rDFT (y = yr@Ci + yi@Si)
    gyr = gb @ Ci.T
    gyi = gb @ Si.T
    dwr = jnp.einsum("bpf,bqf->pqf", gyr, xr) + jnp.einsum(
        "bpf,bqf->pqf", gyi, xi)
    dwi = -jnp.einsum("bpf,bqf->pqf", gyr, xi) + jnp.einsum(
        "bpf,bqf->pqf", gyi, xr)
    return dwr, dwi


def _act_bwd(activation: str, z: jax.Array, g: jax.Array) -> jax.Array:
    """gz = g · act'(z), via jax.vjp so every epilogue stays exact."""
    if activation == "none":
        return g
    _, vjp = jax.vjp(lambda t: apply_activation(t, activation), z)
    return vjp(g.astype(z.dtype))[0]


# ---------------------------------------------------------------------------
# Time-domain-parameter op (training path): differentiable in (x, w, bias)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bc_matmul2d(interpret: bool, activation: str, x2d: jax.Array,
                 w: jax.Array, bias2d: Optional[jax.Array]) -> jax.Array:
    p, q, k = w.shape
    wr, wi = freq_weights(w)
    y = _run_kernel(x2d, wr, wi, bias2d, k, activation, interpret)
    return y[:, : p * k]


def _fwd(interpret, activation, x2d, w, bias2d):
    p, q, k = w.shape
    wr, wi = freq_weights(w)
    # recompute-under-grad: pre-activation z is the residual; the epilogue
    # activation runs unfused so its input is available to the VJP. The
    # forward's (wr, wi) ride in the residuals so the backward never issues
    # a second rfft of the weight table.
    z = _run_kernel(x2d, wr, wi, bias2d, k, "none", interpret)[:, : p * k]
    return (apply_activation(z, activation).astype(x2d.dtype),
            (x2d, w, bias2d, z, wr, wi))


def _bwd(interpret, activation, res, g):
    x2d, w, bias2d, z, wr, wi = res
    p, q, k = w.shape
    gz = _act_bwd(activation, z, g)
    dx = _dx_via_kernel(gz, wr, wi, k, q, interpret).astype(x2d.dtype)
    # transposed-geometry kernel: dw folded back to the time domain inside
    # the launch (dw = dwr@Cᵀ + dwi@Sᵀ in the final-batch epilogue)
    dw = _dw_via_kernel(x2d, gz, p, q, k, interpret).astype(w.dtype)
    db = None
    if bias2d is not None:
        db = gz.sum(0, keepdims=True).astype(bias2d.dtype)
    return dx, dw, db


_bc_matmul2d.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Frozen frequency-weight op (inference / plan path): differentiable in
# (x, wr, wi, bias) — no fft primitive anywhere in its jaxpr
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _bc_freq2d(interpret: bool, activation: str, k: int, p: int,
               tiles: Optional[Tuple[int, int]],
               x2d: jax.Array, wr: jax.Array, wi: jax.Array,
               bias2d: Optional[jax.Array]) -> jax.Array:
    y = _run_kernel(x2d, wr, wi, bias2d, k, activation, interpret, tiles)
    return y[:, : p * k]


def _freq_fwd(interpret, activation, k, p, tiles, x2d, wr, wi, bias2d):
    z = _run_kernel(x2d, wr, wi, bias2d, k, "none", interpret,
                    tiles)[:, : p * k]
    y = apply_activation(z, activation).astype(x2d.dtype)
    return y, (x2d, wr, wi, bias2d, z)


def _freq_bwd(interpret, activation, k, p, tiles, res, g):
    x2d, wr, wi, bias2d, z = res
    P, Q, _ = wr.shape
    q = x2d.shape[1] // k
    gz = _act_bwd(activation, z, g)
    dx = _dx_via_kernel(gz, wr, wi, k, q, interpret).astype(x2d.dtype)
    dwr, dwi = _dw_via_kernel(x2d, gz, P, Q, k, interpret, freq_out=True)
    db = None
    if bias2d is not None:
        # gz spans the padded P·k columns; the bias only the true p·k
        db = gz[:, : bias2d.shape[1]].sum(0, keepdims=True).astype(
            bias2d.dtype)
    return dx, dwr.astype(wr.dtype), dwi.astype(wi.dtype), db


_bc_freq2d.defvjp(_freq_fwd, _freq_bwd)


def _bc_freq_quant2d(interpret: bool, activation: str, k: int, p: int,
                     tiles: Optional[Tuple[int, int]],
                     x2d: jax.Array, wr: jax.Array, wi: jax.Array,
                     w_scale: jax.Array,
                     bias2d: Optional[jax.Array]) -> jax.Array:
    """Primal-only int8 frozen path: wr/wi int8 + per-block f32 scales,
    dequantized inside the kernel. Serving is inference-only here — QAT
    trains through ``quant.fake_quant_symmetric`` on fp32 tables instead,
    so this path deliberately carries no VJP (grad through int8 storage
    would be a silent zero)."""
    y = _run_kernel(x2d, wr, wi, bias2d, k, activation, interpret, tiles,
                    w_scale=w_scale)
    return y[:, : p * k]


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def _as_bias2d(bias: Optional[jax.Array]) -> Optional[jax.Array]:
    if bias is None:
        return None
    return bias.reshape(1, -1).astype(jnp.float32)


def block_circulant_matmul(
    x: jax.Array,
    w: Optional[jax.Array],
    *,
    bias: Optional[jax.Array] = None,
    activation: str = "none",
    w_freq: Optional[Tuple[jax.Array, jax.Array]] = None,
    w_scale: Optional[jax.Array] = None,
    k: Optional[int] = None,
    q: Optional[int] = None,
    tiles: Optional[Tuple[int, int]] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Differentiable block-circulant matmul; arbitrary leading batch dims.

    ``bias`` (p·k,) and ``activation`` fuse into the kernel epilogue.
    ``w_freq=(wr, wi)`` — precomputed real/imag rfft(w), shape (p, q, K) —
    selects the frozen frequency path (no fft in the traced step); pass
    ``k`` alongside when w is None (K alone is ambiguous for odd k), and
    the true ``q`` plus the frozen ``tiles=(pt, qt)`` when wr/wi are
    tile-padded along the block axes (plans). ``w_scale`` (p, q) f32 marks
    the frozen tables as int8 with per-block symmetric scales, dequantized
    inside the kernel (inference-only: no VJP on the quantized path).
    """
    interpret = resolve_interpret(interpret)
    if w_scale is not None and w_freq is None:
        raise ValueError("w_scale only applies to frozen w_freq tables")
    if w_freq is not None:
        wr, wi = w_freq
        p = wr.shape[0]
        if k is None:
            k = 2 * (wr.shape[-1] - 1) if w is None else w.shape[-1]
        if q is None:
            q = wr.shape[1]
    else:
        p, q, k = w.shape
    if x.shape[-1] != q * k:
        # _run_kernel pads x up to padded weights; a caller-side width
        # mismatch against the TRUE q is a miswiring, never padding.
        raise ValueError(
            f"x feature dim {x.shape[-1]} is incompatible with block "
            f"tables (q={q}, k={k}): expected exactly q*k={q * k}"
        )
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    b2d = _as_bias2d(bias)
    if w_freq is not None and w_scale is not None:
        y = _bc_freq_quant2d(interpret, activation, int(k), int(p),
                             tiles, x2d, wr, wi, w_scale, b2d)
    elif w_freq is not None:
        y = _bc_freq2d(interpret, activation, int(k), int(p),
                       tiles, x2d, wr, wi, b2d)
    else:
        y = _bc_matmul2d(interpret, activation, x2d, w, b2d)
    return y.reshape(*lead, p * k)


def block_circulant_matmul_multi(
    x: jax.Array,
    ws: Optional[Sequence[jax.Array]],
    *,
    biases: Optional[Sequence[Optional[jax.Array]]] = None,
    activation: str = "none",
    w_freqs: Optional[Sequence[Tuple[jax.Array, jax.Array]]] = None,
    w_freq_cat: Optional[Tuple[jax.Array, jax.Array]] = None,
    w_scale_cat: Optional[jax.Array] = None,
    splits: Optional[Sequence[int]] = None,
    bias_cat: Optional[jax.Array] = None,
    k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> List[jax.Array]:
    """N projections sharing one input -> ONE stacked-p kernel launch.

    All tables must share (q, k); outputs are split back per projection.
    This is the C-LSTM gate fusion / attention QKV fusion primitive: instead
    of N grid pipelines each re-streaming the same x tiles, the concatenated
    (Σp_i, q, k) table amortizes the forward DFT of x and the pipeline setup
    across every projection.

    ``w_freq_cat=(wr, wi)`` + ``splits`` + ``k`` (and optionally
    ``bias_cat``) take the table already stacked — the pre-concatenated
    frozen group ``plan.freeze_params`` builds at serve-load time — so the
    traced launch contains no weight-side concatenate at all.
    ``w_scale_cat`` (Σp_i, q) f32 marks the stacked tables as int8
    (quantization commutes with p-axis stacking: scales are per-block).
    """
    if w_scale_cat is not None and w_freq_cat is None:
        raise ValueError("w_scale_cat only applies to w_freq_cat tables")
    if w_freq_cat is not None:
        if splits is None or k is None:
            raise ValueError("w_freq_cat needs explicit splits and k")
        if biases is not None:
            raise ValueError("w_freq_cat takes bias_cat, not per-proj biases")
        ps = [int(p) for p in splits]
        y = block_circulant_matmul(
            x, None, bias=bias_cat, activation=activation,
            w_freq=w_freq_cat, w_scale=w_scale_cat, k=k, interpret=interpret,
        )
        return split_outputs(y, ps, k)
    if w_freqs is not None:
        ps = [wr.shape[0] for wr, _ in w_freqs]
        if k is None:
            if ws is not None:
                k = ws[0].shape[-1]
            else:
                k = 2 * (w_freqs[0][0].shape[-1] - 1)
        w_cat = None
        wf_cat = (jnp.concatenate([wr for wr, _ in w_freqs], axis=0),
                  jnp.concatenate([wi for _, wi in w_freqs], axis=0))
    else:
        ps = [w.shape[0] for w in ws]
        k = ws[0].shape[-1]
        w_cat = jnp.concatenate(list(ws), axis=0)
        wf_cat = None
    bias_cat = concat_biases(ps, biases, k)
    y = block_circulant_matmul(
        x, w_cat, bias=bias_cat, activation=activation, w_freq=wf_cat,
        k=k, interpret=interpret,
    )
    return split_outputs(y, ps, k)
