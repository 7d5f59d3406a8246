"""Block-circulant (SWM) linear algebra — the paper's core technique.

A weight matrix ``W ∈ R^{m×n}`` is partitioned into ``p×q`` square blocks of
size ``k`` (``p = m/k``, ``q = n/k``). Each block ``W_ij`` is a circulant
matrix defined by one length-``k`` vector ``w_ij`` (the paper, §3):

    W_ij @ x_j = IFFT( FFT(w_ij) ∘ FFT(x_j) )            (circulant-conv thm)

giving O(n log n) compute and O(n) storage per layer instead of O(n²).

Convention: ``W_ij`` is the circulant matrix whose **first column** is
``w_ij``, i.e. ``W_ij[a, b] = w_ij[(a - b) mod k]`` so ``W_ij @ x`` is the
*circular convolution* ``w ⊛ x`` and the FFT identity above holds exactly.
(The paper's prose says "first row"; with a first-row convention the product
is a circular *correlation*, which is the same family under index reversal —
the trained parameterization is isomorphic. We use the convolution
convention so the stated FFT identity is literally true.)

Four forward implementations, selectable per layer (``impl=``):

  * ``paper``  — faithful to the ASIC dataflow (§5.2):
                 ``y_i = Σ_j IFFT(ŵ_ij ∘ x̂_j)`` — one inverse transform per
                 (i, j) block, accumulated in the **time** domain.
  * ``freq``   — beyond-paper: accumulate in the **frequency** domain, one
                 IFFT per output block: ``y_i = IFFT(Σ_j ŵ_ij ∘ x̂_j)``.
                 q× fewer inverse transforms; bit-identical math (linearity).
  * ``dft``    — TPU-native: the (r)DFT of a length-k block is a small dense
                 matmul against precomputed real cos/sin bases → runs on the
                 MXU. Frequency contraction is a per-bin complex GEMM.
  * ``pallas`` — fused Pallas TPU kernel (see repro.kernels.block_circulant);
                 the Pallas interpreter runs it only on the CPU backend.

All paths share the parameterization: the *time-domain* block table
``w ∈ R^{p×q×k}`` is the trainable parameter (so standard optimizers apply);
inference may precompute ``rfft(w)`` once ("frozen frequency weights" — the
paper stores FFT(w_ij) in BRAM).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "blocks_to_dense",
    "dense_to_blocks_lstsq",
    "block_circulant_matvec_paper",
    "block_circulant_matvec_freq",
    "block_circulant_matvec_dft",
    "block_circulant_apply",
    "block_circulant_apply_fused",
    "block_circulant_apply_multi",
    "dft_bases",
    "dft_bases_adjoint",
    "valid_block_size",
    "swm_flops",
    "dense_flops",
]


# ---------------------------------------------------------------------------
# Reference / conversion utilities
# ---------------------------------------------------------------------------


def blocks_to_dense(w: jax.Array) -> jax.Array:
    """Expand the block table ``w (p, q, k)`` to the dense ``(p·k, q·k)`` W.

    ``W[i·k + a, j·k + b] = w[i, j, (a - b) mod k]``.
    Oracle only — never used in the hot path.
    """
    p, q, k = w.shape
    a = jnp.arange(k)
    idx = (a[:, None] - a[None, :]) % k            # (k, k): (a-b) mod k
    blocks = w[:, :, idx]                           # (p, q, k, k)
    return jnp.transpose(blocks, (0, 2, 1, 3)).reshape(p * k, q * k)


def dense_to_blocks_lstsq(W: jax.Array, k: int) -> jax.Array:
    """Project a dense matrix to the nearest block-circulant table (Frobenius).

    The least-squares circulant fit of a k×k block B is the mean over its
    circulant diagonals: ``w[d] = mean_a B[a, (a - d) mod k]``. Used to
    initialize SWM layers from dense checkpoints (post-training compression).
    """
    m, n = W.shape
    if m % k or n % k:
        raise ValueError(f"dims ({m},{n}) not divisible by k={k}")
    p, q = m // k, n // k
    blocks = W.reshape(p, k, q, k).transpose(0, 2, 1, 3)  # (p, q, k, k)
    a = jnp.arange(k)
    # For diagonal d, entries B[a, (a-d) mod k].
    cols = (a[None, :] - a[:, None]) % k                   # (d, a) -> col
    gathered = blocks[:, :, a[None, :], cols]              # (p, q, k_d, k_a)
    return gathered.mean(-1)


def valid_block_size(requested: int, *dims: int) -> int:
    """Largest k ≤ requested dividing every dim (the paper requires k | m, n).

    Falls back through divisors; k=1 (dense-equivalent storage layout) is the
    floor. Configs use this so e.g. d_ff=11008 clamps k=128 → 32.
    """
    import math

    g = 0
    for d in dims:
        g = math.gcd(g, int(d))
    k = min(max(1, int(requested)), g)
    while g % k:
        k -= 1
    return k


# ---------------------------------------------------------------------------
# FFT-path forwards
# ---------------------------------------------------------------------------


def _split_blocks(x: jax.Array, k: int) -> jax.Array:
    """(..., n) -> (..., q, k)."""
    *lead, n = x.shape
    assert n % k == 0, (n, k)
    return x.reshape(*lead, n // k, k)


def _sharded_fft(fn, x: jax.Array) -> jax.Array:
    """Run an FFT shard-locally over the DP axes via shard_map.

    GSPMD replicates `fft` ops (all-gathers every sharded operand — §Perf 1);
    but the transform axis is never sharded here, so each shard can FFT its
    slice independently. When a production mesh is registered
    (dist.sharding.set_ambient_mesh) we wrap the op in shard_map over the
    data axes; otherwise this is a plain call. This rescues the
    paper-faithful O(n log n) dataflow for distributed training
    (impl='freq_shmap' / 'paper_shmap').
    """
    from repro.dist.sharding import _AMBIENT_MESH, data_axes
    from jax.sharding import PartitionSpec as P

    mesh = _AMBIENT_MESH[0]
    if mesh is None:
        return fn(x)
    dp = data_axes(mesh)
    if not dp or x.shape[0] % max(
        1, int(np.prod([mesh.shape[a] for a in dp]))
    ):
        return fn(x)
    lead = dp if len(dp) > 1 else dp[0]
    spec = P(lead, *([None] * (x.ndim - 1)))
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)


def block_circulant_matvec_paper(
    x: jax.Array, w: jax.Array, *, precision=None
) -> jax.Array:
    """Paper-faithful §5.2 dataflow: IFFT per (i,j) block, time-domain sum.

    x: (..., n), w: (p, q, k) -> (..., m).  Faithful to the ASIC processing
    system ``y_i = Σ_j IFFT(ŵ_ij ∘ x̂_j)``: the accumulator operates on
    time-domain IFFT outputs, one input block j at a time (the hardware
    iterates blocks through one FFT engine), i.e. O(p·q) inverse transforms.
    Implemented as a lax.scan over j so the (..., p, q, k) tensor is never
    materialized — memory-feasible at LM scale while keeping the exact
    operation count of the paper's dataflow.
    """
    p, q, k = w.shape
    xb = _split_blocks(x, k)                               # (..., q, k)
    xh = jnp.fft.rfft(xb.astype(jnp.float32), axis=-1)     # (..., q, K)
    wh = jnp.fft.rfft(w.astype(jnp.float32), axis=-1)      # (p, q, K)

    def body(acc, xs):
        xh_j, wh_j = xs                                    # (..., K), (p, K)
        prod = xh_j[..., None, :] * wh_j                   # (..., p, K)
        acc = acc + jnp.fft.irfft(prod, n=k, axis=-1)      # time-domain sum
        return acc, None

    acc0 = jnp.zeros((*x.shape[:-1], p, k), jnp.float32)
    acc, _ = jax.lax.scan(
        body, acc0, (jnp.moveaxis(xh, -2, 0), jnp.moveaxis(wh, 1, 0))
    )
    return acc.reshape(*x.shape[:-1], p * k).astype(x.dtype)


def block_circulant_matvec_freq(
    x: jax.Array, w: jax.Array, *, w_freq: Optional[jax.Array] = None,
    k: Optional[int] = None, shmap: bool = False,
) -> jax.Array:
    """Frequency-domain accumulation (beyond-paper): one IFFT per output block.

    ``y_i = IFFT( Σ_j ŵ_ij ∘ x̂_j )``. ``w_freq`` (p, q, K) complex may be
    passed to use frozen precomputed weights (inference; the paper's BRAM) —
    pass ``k`` alongside when w is None (K alone is ambiguous for odd k).
    ``shmap=True`` runs the activation FFTs shard-locally over the DP axes
    (see _sharded_fft) — the faithful O(n log n) dataflow, distributable.
    """
    if w_freq is None:
        p, q, k = w.shape
        w_freq = jnp.fft.rfft(w.astype(jnp.float32), axis=-1)
    else:
        p, q = w_freq.shape[:2]
        if k is None:
            k = (w_freq.shape[-1] - 1) * 2 if w is None else w.shape[-1]
    xb = _split_blocks(x, k).astype(jnp.float32)
    fwd = lambda a: jnp.fft.rfft(a, axis=-1)
    xh = _sharded_fft(fwd, xb) if shmap else fwd(xb)       # (..., q, K)
    yh = jnp.einsum("...qf,pqf->...pf", xh, w_freq)        # (..., p, K)
    inv = lambda a: jnp.fft.irfft(a, n=k, axis=-1)
    yb = _sharded_fft(inv, yh) if shmap else inv(yh)       # (..., p, k)
    return yb.reshape(*x.shape[:-1], p * k).astype(x.dtype)


# ---------------------------------------------------------------------------
# DFT-as-matmul path (MXU-native)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _dft_bases_np(k: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Real rDFT analysis/synthesis bases as numpy constants.

    Analysis (x (.., k) real -> X (.., K) complex, K = k//2+1):
        Xr = x @ C,   Xi = x @ S          C[a,f]=cos(2πaf/k), S[a,f]=-sin(2πaf/k)
    Synthesis (X -> y (.., k) real):
        y = Xr @ Ci + Xi @ Si
        Ci[f,a] = g_f·cos(2πaf/k)/k,  Si[f,a] = -g_f·sin(2πaf/k)/k
        g_f = 1 for f ∈ {0, k/2}, else 2   (Hermitian-symmetry fold)
    """
    K = k // 2 + 1
    a = np.arange(k)[:, None]
    f = np.arange(K)[None, :]
    ang = 2.0 * np.pi * a * f / k
    C = np.cos(ang)
    S = -np.sin(ang)
    g = np.full((K,), 2.0)
    g[0] = 1.0
    if k % 2 == 0:
        g[-1] = 1.0
    Ci = (g[:, None] * np.cos(ang).T) / k
    Si = -(g[:, None] * np.sin(ang).T) / k
    return (
        C.astype(np.float32),
        S.astype(np.float32),
        Ci.astype(np.float32),
        Si.astype(np.float32),
    )


def dft_bases(k: int, dtype=jnp.float32):
    C, S, Ci, Si = _dft_bases_np(k)
    return (
        jnp.asarray(C, dtype),
        jnp.asarray(S, dtype),
        jnp.asarray(Ci, dtype),
        jnp.asarray(Si, dtype),
    )


@functools.lru_cache(maxsize=64)
def _dft_bases_adjoint_np(k: int):
    C, S, Ci, Si = _dft_bases_np(k)
    return (C, S, np.ascontiguousarray(Ci.T), np.ascontiguousarray(Si.T),
            np.ascontiguousarray(C.T), np.ascontiguousarray(S.T))


def dft_bases_adjoint(k: int, dtype=jnp.float32):
    """Basis set for the transposed-geometry weight-adjoint (dw) kernel.

    Returns ``(C, S, CiT, SiT, CT, ST)``:

      * ``C, S``     — analysis bases for x̂ (as :func:`dft_bases`),
      * ``CiT, SiT`` — adjoint of the inverse rDFT, applied to the upstream
        cotangent g: ``gyr = g @ Ciᵀ``, ``gyi = g @ Siᵀ`` (the pullback of
        ``y = yr@Ci + yi@Si``),
      * ``CT, ST``   — adjoint of the forward rDFT, folding the frequency
        cotangent back to the time domain: ``dw = dwr@Cᵀ + dwi@Sᵀ``.

    Precomputed as numpy constants (lru-cached) so the dw kernel launch
    carries no per-trace transpose of the basis matrices.
    """
    C, S, CiT, SiT, CT, ST = _dft_bases_adjoint_np(k)
    return tuple(jnp.asarray(a, dtype) for a in (C, S, CiT, SiT, CT, ST))


def _dft_fwd_math(x, w, karatsuba, cdt):
    p, q, k = w.shape
    C, S, Ci, Si = dft_bases(k, cdt)
    f32 = jnp.float32
    xb = _split_blocks(x, k).astype(cdt)                   # (..., q, k)
    wf = w.astype(cdt)
    mm = functools.partial(jnp.matmul, preferred_element_type=f32)
    xr = mm(xb, C).astype(cdt)                             # (..., q, K)
    xi = mm(xb, S).astype(cdt)
    wr = mm(wf, C).astype(cdt)                             # (p, q, K)
    wi = mm(wf, S).astype(cdt)
    ein = functools.partial(jnp.einsum, preferred_element_type=f32)
    if karatsuba:
        # (xr + i·xi)(wr + i·wi): t1 = xr·wr, t2 = xi·wi,
        # yr = t1 - t2, yi = (xr+xi)(wr+wi) - t1 - t2
        t1 = ein("...qf,pqf->...pf", xr, wr)
        t2 = ein("...qf,pqf->...pf", xi, wi)
        t3 = ein("...qf,pqf->...pf", xr + xi, wr + wi)
        yr = (t1 - t2).astype(cdt)
        yi = (t3 - t1 - t2).astype(cdt)
    else:
        yr = (ein("...qf,pqf->...pf", xr, wr)
              - ein("...qf,pqf->...pf", xi, wi)).astype(cdt)
        yi = (ein("...qf,pqf->...pf", xr, wi)
              + ein("...qf,pqf->...pf", xi, wr)).astype(cdt)
    yb = mm(yr, Ci) + mm(yi, Si)                           # (..., p, k) f32
    return yb.reshape(*x.shape[:-1], p * k).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dft_op(x2d: jax.Array, w: jax.Array, karatsuba: bool) -> jax.Array:
    """2-D core of the DFT path with a hand-written VJP.

    XLA's autodiff of the frequency einsums materializes (K, p, tokens)
    cotangent transposes in f32 (measured 320 GB/dev on gemma3 train_4k).
    The custom VJP computes the circulant adjoints with bf16 operands and
    f32 accumulation, residuals = just (x, w) — frequency tensors are
    recomputed, never stored.
    """
    return _dft_fwd_math(x2d, w, karatsuba, x2d.dtype)


def _dft_fwd(x2d, w, karatsuba):
    return _dft_op(x2d, w, karatsuba), (x2d, w)


def _dft_bwd(karatsuba, res, g):
    x2d, w = res
    p, q, k = w.shape
    cdt = x2d.dtype
    f32 = jnp.float32
    C, S, Ci, Si = dft_bases(k, cdt)
    mm = functools.partial(jnp.matmul, preferred_element_type=f32)
    ein = functools.partial(jnp.einsum, preferred_element_type=f32)
    # recompute frequency operands (cheap small matmuls)
    xb = _split_blocks(x2d, k).astype(cdt)
    xr = mm(xb, C).astype(cdt)
    xi = mm(xb, S).astype(cdt)
    wf = w.astype(cdt)
    wr = mm(wf, C).astype(cdt)
    wi = mm(wf, S).astype(cdt)
    gb = g.reshape(*g.shape[:-1], p, k).astype(cdt)
    # adjoint of the inverse rDFT (y = yr@Ci + yi@Si)
    gyr = mm(gb, Ci.T).astype(cdt)                         # (..., p, K)
    gyi = mm(gb, Si.T).astype(cdt)
    # adjoints of the per-bin complex GEMM
    dxr = (ein("...pf,pqf->...qf", gyr, wr)
           + ein("...pf,pqf->...qf", gyi, wi)).astype(cdt)
    dxi = (-ein("...pf,pqf->...qf", gyr, wi)
           + ein("...pf,pqf->...qf", gyi, wr)).astype(cdt)
    dwr = (ein("...pf,...qf->pqf", gyr, xr)
           + ein("...pf,...qf->pqf", gyi, xi))
    dwi = (-ein("...pf,...qf->pqf", gyr, xi)
           + ein("...pf,...qf->pqf", gyi, xr))
    # adjoint of the forward rDFT (xr = x@C, xi = x@S)
    dx = (mm(dxr, C.T) + mm(dxi, S.T)).reshape(x2d.shape).astype(x2d.dtype)
    dw = (mm(dwr.astype(cdt), C.T)
          + mm(dwi.astype(cdt), S.T)).astype(w.dtype)
    return dx, dw


_dft_op.defvjp(_dft_fwd, _dft_bwd)


def block_circulant_matvec_dft(
    x: jax.Array,
    w: jax.Array,
    *,
    karatsuba: bool = False,
    compute_dtype=None,
) -> jax.Array:
    """MXU path: rDFT via dense matmul, per-bin complex GEMM, inverse matmul.

    Every op is a matmul or einsum → maps onto the systolic array. With
    ``karatsuba=True`` the complex contraction uses 3 real einsums instead
    of 4 (beyond-paper micro-optimization; measured in §Perf).

    Multiplications run in the input dtype (bf16 in production) with f32
    accumulation; the custom VJP keeps backward intermediates in the same
    dtype and saves only (x, w) as residuals (§Perf iterations 2–3).
    """
    if compute_dtype is not None and compute_dtype != x.dtype:
        x = x.astype(compute_dtype)
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    y = _dft_op(x2d, w, bool(karatsuba))
    return y.reshape(*lead, y.shape[-1])


# ---------------------------------------------------------------------------
# Fused pair op: two circulant projections sharing one forward DFT
# (SwiGLU's wi/wu read the same x — the x̂ transform is computed once,
#  saving ~1/3 of the FFN's forward transforms; §Perf "further levers")
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _dft_pair_op(x2d: jax.Array, w1: jax.Array, w2: jax.Array):
    y1, y2, _, _ = _dft_pair_fwd_math(x2d, w1, w2)
    return y1, y2


def _dft_pair_fwd_math(x2d, w1, w2):
    p, q, k = w1.shape
    cdt = x2d.dtype
    C, S, Ci, Si = dft_bases(k, cdt)
    f32 = jnp.float32
    mm = functools.partial(jnp.matmul, preferred_element_type=f32)
    ein = functools.partial(jnp.einsum, preferred_element_type=f32)
    xb = _split_blocks(x2d, k).astype(cdt)
    xr = mm(xb, C).astype(cdt)          # shared forward transform
    xi = mm(xb, S).astype(cdt)

    def one(w):
        wf = w.astype(cdt)
        wr = mm(wf, C).astype(cdt)
        wi = mm(wf, S).astype(cdt)
        yr = (ein("...qf,pqf->...pf", xr, wr)
              - ein("...qf,pqf->...pf", xi, wi)).astype(cdt)
        yi = (ein("...qf,pqf->...pf", xr, wi)
              + ein("...qf,pqf->...pf", xi, wr)).astype(cdt)
        y = mm(yr, Ci) + mm(yi, Si)
        return y.reshape(*x2d.shape[:-1], w.shape[0] * k).astype(x2d.dtype)

    return one(w1), one(w2), xr, xi


def _dft_pair_fwd(x2d, w1, w2):
    y1, y2, _, _ = _dft_pair_fwd_math(x2d, w1, w2)
    return (y1, y2), (x2d, w1, w2)


def _dft_pair_bwd(res, gs):
    x2d, w1, w2 = res
    g1, g2 = gs
    dx1, dw1 = _dft_bwd(False, (x2d, w1), g1)
    dx2, dw2 = _dft_bwd(False, (x2d, w2), g2)
    return dx1 + dx2, dw1, dw2


_dft_pair_op.defvjp(_dft_pair_fwd, _dft_pair_bwd)


@jax.named_scope("circulant")
def block_circulant_apply_pair(x: jax.Array, w1: jax.Array, w2: jax.Array):
    """(y1, y2) = (BC(w1)·x, BC(w2)·x) with one shared forward DFT."""
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    y1, y2 = _dft_pair_op(x2d, w1, w2)
    return (y1.reshape(*lead, y1.shape[-1]),
            y2.reshape(*lead, y2.shape[-1]))


# ---------------------------------------------------------------------------
# Unified entry point
# ---------------------------------------------------------------------------


@jax.named_scope("circulant")
def block_circulant_apply(
    x: jax.Array,
    w: jax.Array,
    *,
    impl: str = "freq",
    karatsuba: bool = False,
) -> jax.Array:
    """Dispatch on implementation. x: (..., q·k), w: (p, q, k) -> (..., p·k)."""
    if impl == "paper":
        return block_circulant_matvec_paper(x, w)
    if impl == "freq":
        return block_circulant_matvec_freq(x, w)
    if impl == "freq_shmap":
        lead = x.shape[:-1]
        y = block_circulant_matvec_freq(
            x.reshape(-1, x.shape[-1]), w, shmap=True)
        return y.reshape(*lead, y.shape[-1])
    if impl == "dft":
        return block_circulant_matvec_dft(x, w, karatsuba=karatsuba)
    if impl == "pallas":
        from repro.kernels.block_circulant import ops as bc_ops

        return bc_ops.block_circulant_matmul(x, w)
    raise ValueError(f"unknown impl {impl!r}")


def _epilogue(y: jax.Array, bias: Optional[jax.Array], activation: str
              ) -> jax.Array:
    from repro.kernels.block_circulant.kernel import apply_activation

    if bias is not None:
        y = y + bias.astype(y.dtype)
    return apply_activation(y, activation)


def dequantize_freq_pair(wr: jax.Array, wi: jax.Array,
                         w_scale: Optional[jax.Array]):
    """int8 frozen pair + per-(p, q)-block scale -> f32 pair (no-op when
    ``w_scale`` is None). The XLA ``dft``/``freq`` fallback's analogue of
    the Pallas kernel's in-tile dequant: identical float ops
    (``quant.dequantize_symmetric``), so both paths see the same f32
    tables and greedy outputs stay bit-identical across impls."""
    if w_scale is None:
        return wr, wi
    from repro.core.quant import dequantize_symmetric

    return (dequantize_symmetric(wr, w_scale),
            dequantize_symmetric(wi, w_scale))


@jax.named_scope("circulant")
def block_circulant_apply_fused(
    x: jax.Array,
    w: Optional[jax.Array],
    *,
    impl: str = "freq",
    bias: Optional[jax.Array] = None,
    activation: str = "none",
    w_freq: Optional[Tuple[jax.Array, jax.Array]] = None,
    w_scale: Optional[jax.Array] = None,
    k: Optional[int] = None,
    karatsuba: bool = False,
) -> jax.Array:
    """One projection with the bias/activation epilogue and (optionally)
    frozen frequency weights ``w_freq=(wr, wi)``.

    * ``impl='pallas'`` — everything fuses into the kernel (epilogue runs in
      VMEM before writeback; frozen weights skip rfft(w) entirely;
      ``w_scale`` marks int8 tables dequantized in-tile).
    * other impls — frozen weights route through the freq path (the paper's
      BRAM-resident FFT(w)); int8 tables dequantize at trace entry
      (:func:`dequantize_freq_pair`); epilogue is a trailing XLA
      elementwise (fused by XLA itself).
    """
    if impl == "pallas":
        from repro.kernels.block_circulant import ops as bc_ops

        return bc_ops.block_circulant_matmul(
            x, w, bias=bias, activation=activation, w_freq=w_freq,
            w_scale=w_scale, k=k
        )
    if w_freq is not None:
        wr, wi = dequantize_freq_pair(*w_freq, w_scale)
        lead = x.shape[:-1]
        y = block_circulant_matvec_freq(
            x.reshape(-1, x.shape[-1]), w,
            w_freq=(wr + 1j * wi).astype(jnp.complex64), k=k,
        )
        y = y.reshape(*lead, y.shape[-1])
    else:
        y = block_circulant_apply(x, w, impl=impl, karatsuba=karatsuba)
    return _epilogue(y, bias, activation)


def concat_biases(splits, biases, k: int) -> Optional[jax.Array]:
    """Stack per-projection biases along the fused p axis (None -> zeros).

    Single source of truth for the stacked-p bias convention, shared by the
    XLA multi path here, ``ops.block_circulant_matmul_multi`` and
    ``plan.build_multi_plan``.
    """
    if biases is None or not any(b is not None for b in biases):
        return None
    parts = [
        (jnp.zeros((p * k,), jnp.float32) if b is None
         else b.reshape(-1).astype(jnp.float32))
        for p, b in zip(splits, biases)
    ]
    return jnp.concatenate(parts)


def split_outputs(y: jax.Array, splits, k: int):
    """Slice a fused (..., Σp_i·k) output back into per-projection outputs."""
    outs = []
    off = 0
    for p in splits:
        outs.append(y[..., off: off + p * k])
        off += p * k
    return outs


@jax.named_scope("circulant")
def block_circulant_apply_multi(
    x: jax.Array,
    ws,
    *,
    impl: str = "freq",
    biases=None,
    activation: str = "none",
    w_freqs=None,
    w_freq_cat: Optional[Tuple[jax.Array, jax.Array]] = None,
    w_scale_cat: Optional[jax.Array] = None,
    splits: Optional[Tuple[int, ...]] = None,
    bias_cat: Optional[jax.Array] = None,
    k: Optional[int] = None,
    karatsuba: bool = False,
):
    """N projections sharing one input -> one stacked-p launch, any impl.

    Tables concatenate along p (they must share (q, k)), so the shared
    input is transformed once and a single contraction/kernel serves every
    projection — C-LSTM's fused gate dataflow, applied to LSTM gates and
    attention QKV. Returns the per-projection outputs (split back). Pass
    ``k`` when ws is None and the block size is odd (K is ambiguous).

    ``w_freq_cat=(wr, wi)`` takes a PRE-concatenated stacked frozen table
    (``plan.freeze_params`` attaches one per fused group under
    ``plan.FUSED_KEY``) with explicit per-projection ``splits`` (p_i block
    counts) and ``k`` — the zero-concat serve path: no weight-side
    ``jnp.concatenate`` appears in the trace. ``bias_cat`` is the matching
    pre-concatenated (Σp_i·k,) bias (mutually exclusive with ``biases``);
    ``w_scale_cat`` the matching stacked per-block scales when the fused
    tables are int8.
    """
    if w_freq_cat is not None:
        if splits is None or k is None:
            raise ValueError("w_freq_cat needs explicit splits and k")
        if biases is not None:
            raise ValueError("w_freq_cat takes bias_cat, not per-proj biases")
    if impl == "pallas":
        from repro.kernels.block_circulant import ops as bc_ops

        return bc_ops.block_circulant_matmul_multi(
            x, ws, biases=biases, activation=activation, w_freqs=w_freqs,
            w_freq_cat=w_freq_cat, w_scale_cat=w_scale_cat, splits=splits,
            bias_cat=bias_cat, k=k,
        )
    if w_freq_cat is not None:
        wr, wi = dequantize_freq_pair(*w_freq_cat, w_scale_cat)
        ps = list(splits)
        lead = x.shape[:-1]
        y = block_circulant_matvec_freq(
            x.reshape(-1, x.shape[-1]), None,
            w_freq=(wr + 1j * wi).astype(jnp.complex64), k=k,
        ).reshape(*lead, -1)
        if bias_cat is not None:
            y = y + bias_cat.astype(y.dtype)
        return [
            _epilogue(o, None, activation)
            for o in split_outputs(y, ps, k)
        ]
    if w_freqs is not None:
        ps = [wr.shape[0] for wr, _ in w_freqs]
        if k is None:
            k = (ws[0].shape[-1] if ws is not None
                 else 2 * (w_freqs[0][0].shape[-1] - 1))
        wf_cat = jnp.concatenate(
            [(wr + 1j * wi).astype(jnp.complex64) for wr, wi in w_freqs],
            axis=0,
        )
        lead = x.shape[:-1]
        y = block_circulant_matvec_freq(
            x.reshape(-1, x.shape[-1]), None, w_freq=wf_cat, k=k
        ).reshape(*lead, -1)
    else:
        ps = [w.shape[0] for w in ws]
        k = ws[0].shape[-1]
        y = block_circulant_apply(
            x, jnp.concatenate(list(ws), axis=0), impl=impl,
            karatsuba=karatsuba,
        )
    return [
        _epilogue(o, biases[i] if biases is not None else None, activation)
        for i, o in enumerate(split_outputs(y, ps, k))
    ]


# ---------------------------------------------------------------------------
# FLOP accounting (roofline / benchmarks)
# ---------------------------------------------------------------------------


def dense_flops(batch: int, m: int, n: int) -> int:
    return 2 * batch * m * n


def swm_flops(batch: int, m: int, n: int, k: int, impl: str = "freq") -> int:
    """Analytic FLOPs of one SWM layer application (fwd)."""
    p, q, K = m // k, n // k, k // 2 + 1
    fft = 5 * k * int(np.log2(max(k, 2)))   # ~5k·log2 k per length-k rFFT
    contraction = 8 * p * q * K             # complex MAC = 4 mul + 4 add
    if impl == "paper":
        iffts = p * q
    else:
        iffts = p
    return batch * (q * fft + contraction + iffts * fft)
