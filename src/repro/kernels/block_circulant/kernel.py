"""Pallas TPU kernel: fused block-circulant matmul in the frequency domain.

TPU adaptation of the paper's FPGA/ASIC dataflow (§5):

  FPGA/ASIC                               this kernel
  ---------                               -----------
  FFT butterfly units (depth log k)   →   rDFT as a k×K dense matmul on the
                                          MXU (K = k//2+1); at k=128 the
                                          transform is a single 128-wide
                                          systolic pass.
  BRAM-resident FFT(w) weights        →   frequency-domain weights (wr, wi)
                                          precomputed once outside the kernel
                                          and streamed HBM→VMEM tile by tile.
  ∘-multiply + accumulator            →   per-frequency-bin complex GEMM over
                                          the q (input-block) grid axis,
                                          accumulated in VMEM scratch (f32).
  DDR→BRAM ping-pong buffers          →   Pallas grid pipeline: BlockSpec
                                          double-buffers the next (x, w) tiles
                                          while the MXU consumes the current.
  IFFT + bias/activation peripheral   →   inverse rDFT matmul fused into the
                                          same kernel on the final q step,
                                          followed by the fused epilogue
                                          (bias add + activation) before the
                                          VMEM→HBM writeback.
  One pipeline per gate matrix        →   stacked-p multi-projection: several
                                          projections sharing one input (LSTM
                                          gates, attention QKV) concatenate
                                          their frequency tables along p and
                                          run as ONE kernel launch (see
                                          ops.block_circulant_matmul_multi).

Grid: ``(B/bB, p/pt, q/qt)`` with q innermost, so the frequency-domain
accumulator lives in VMEM scratch across the contraction.

Quantized tables (the paper's 12–16-bit fixed-point results, §4): frozen
(wr, wi) may instead be stored int8 with one symmetric f32 scale per
(p, q) circulant block, shared across the K frequency bins and the re/im
pair (``quant.symmetric_scales``). The int8 tiles stream HBM→VMEM at 1/4
the fp32 bandwidth and are dequantized *inside* the kernel, on the VMEM
tile, right before the per-bin complex GEMM — a single (pt, qt, 1)
broadcast multiply, the same position the MSR bit-truncation decode holds
between BRAM and the multiplier array in the FPGA pipeline. Tile geometry
is chosen with the fp32 ``vmem_estimate`` either way so quantized and
fp32 plans compile to identically-shaped executables.

The per-bin contraction ``y[b,p,f] += Σ_q x[b,q,f]·w[p,q,f]`` is expressed
as a frequency-batched ``dot_general``; Mosaic unrolls the K batch entries
into 2-D MXU dots. Correctness is validated against
``ref.block_circulant_matmul_ref``: over shape/dtype sweeps in interpret
mode on the CPU, and at qwen3-0.6b's projection shapes on a TPU by
``chip_smoke.py``.

Training adjoints (the paper's training-phase O(n log n) claim):

  * dL/dx — the FORWARD kernel re-launched with the conjugated /
    index-reversed frequency weights (a circulant transpose is the
    index-reversed vector ⇒ conj(ŵ); the block table transposes p ↔ q).
  * dL/dw — :func:`bc_dw_pallas`, the TRANSPOSED-GEOMETRY kernel below:
    ``dŵ[p,q,f] = Σ_b ĝ[b,p,f] · conj(x̂[b,q,f])`` is the same per-bin
    complex GEMM with the train batch promoted to the contraction axis.
    Grid ``(p/pt, q/qt, B/bB)`` with b innermost; the (pt, qt, K)
    frequency cotangent accumulates in VMEM scratch across the batch.
    Both operands transform inside the kernel (g through the adjoint of
    the inverse rDFT ``Ciᵀ/Siᵀ``, x through the analysis bases ``C/S``)
    and the epilogue either folds the cotangent back to the time domain
    (``dw = dwr@Cᵀ + dwi@Sᵀ`` — the `_bwd` path for trainable time-domain
    tables) or writes the (dwr, dwi) pair raw (``freq_out=True`` — the
    `_freq_bwd` path for frozen/plan frequency parameters). No dense
    (B, P, f)×(B, Q, f) outer product is ever materialized in HBM.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bc_matmul_pallas", "bc_dw_pallas", "choose_blocks",
           "choose_batch_block", "choose_blocks_dw", "choose_batch_block_dw",
           "vmem_estimate", "vmem_estimate_dw", "ACTIVATIONS",
           "apply_activation"]

# Epilogue activations fused into the final-q writeback (the paper's
# IFFT + peripheral stage). Keys are the only legal `activation=` values.
ACTIVATIONS = ("none", "relu", "tanh", "sigmoid", "gelu")


def apply_activation(z: jax.Array, activation: str) -> jax.Array:
    """Elementwise epilogue activation. Pure jnp — legal inside the kernel."""
    if activation == "none":
        return z
    if activation == "relu":
        return jnp.maximum(z, 0.0)
    if activation == "tanh":
        return jnp.tanh(z)
    if activation == "sigmoid":
        return jax.nn.sigmoid(z)
    if activation == "gelu":
        return jax.nn.gelu(z)
    raise ValueError(f"unknown activation {activation!r}; one of {ACTIVATIONS}")


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _lanes(n: int) -> int:
    """A trailing VMEM tile dim of ``n`` elements, padded to whole
    128-lane rows."""
    return _cdiv(n, 128) * 128


def vmem_estimate(bB: int, pt: int, qt: int, k: int,
                  quantized: bool = False) -> int:
    """Bytes of VMEM working set for one (bB, pt, qt) tile assignment.

    x tile + (wr, wi) tiles double-buffered, f32 accumulator scratch pair,
    y tile, and the four resident DFT basis matrices. The single source of
    truth shared by :func:`choose_blocks` and benchmarks/kernel_bench.py.

    ``quantized=True`` reports the int8-table working set: the streamed
    (wr, wi) tiles shrink 4× (int8 payload) plus a per-(p, q) f32 scale
    tile, and one f32 dequantized copy of the pair is charged (produced by
    the in-kernel dequant, live only within the grid step, so not
    double-buffered). Tile *selection* (:func:`choose_blocks`) always uses
    the fp32 estimate — quantized and fp32 plans must share identical tile
    geometry so the serve paths compile to the same executables.

    Every trailing tile dim is charged at whole 128-lane rows, as VMEM
    holds it: the K bins of the tables and accumulators, and the block of
    x and y when k is not a multiple of 128, where the block has its own
    axis.
    """
    K, Kl, kl = k // 2 + 1, _lanes(k // 2 + 1), _lanes(k)
    x_t = bB * qt * kl * 4
    if quantized:
        w_t = 2 * pt * qt * Kl * 1 + pt * qt * 128 * 4  # int8 pair + scales
        deq = 2 * pt * qt * Kl * 4                       # in-kernel f32 copy
    else:
        w_t = 2 * pt * qt * Kl * 4
        deq = 0
    acc = 2 * bB * pt * Kl * 4
    y_t = bB * pt * kl * 4
    dft = 2 * k * Kl * 4 + 2 * K * kl * 4
    return 2 * (x_t + w_t) + acc + y_t + dft + deq   # ×2: double buffering


def choose_batch_block(B: int, pt: int, qt: int, k: int,
                       vmem_budget: int = 8 * 1024 * 1024) -> int:
    """Batch tile for FIXED (pt, qt) block tiles — the plan path, where the
    block-axis tiles are frozen into the padded weight layout at build time
    and only the runtime batch varies."""
    bB = min(B, 128)
    while vmem_estimate(bB, pt, qt, k) > vmem_budget and bB > 8:
        bB //= 2
    return bB


def vmem_estimate_dw(bB: int, pt: int, qt: int, k: int) -> int:
    """Bytes of VMEM working set for one (pt, qt, bB) dw-kernel tile.

    x and g tiles double-buffered, the (pt, qt, K) f32 frequency-cotangent
    accumulator pair, the output tile (time-domain dw OR the (dwr, dwi)
    pair — the larger of the two is charged), and the six resident basis
    matrices. Shared by :func:`choose_blocks_dw` and kernel_bench. Tile
    dims are charged at whole 128-lane rows, as in :func:`vmem_estimate`.
    """
    K, Kl, kl = k // 2 + 1, _lanes(k // 2 + 1), _lanes(k)
    x_t = bB * qt * kl * 4
    g_t = bB * pt * kl * 4
    acc = 2 * pt * qt * Kl * 4
    out = max(pt * qt * kl, 2 * pt * qt * Kl) * 4
    dft = 4 * k * Kl * 4 + 2 * K * kl * 4
    return 2 * (x_t + g_t) + acc + out + dft   # ×2: double buffering


def choose_batch_block_dw(B: int, pt: int, qt: int, k: int,
                          vmem_budget: int = 8 * 1024 * 1024) -> int:
    """Batch (contraction) tile for FIXED (pt, qt) dw tiles — the cached
    backward-geometry path, where the block-axis tiles are frozen by
    ``plan.dw_geometry`` and only the runtime batch varies."""
    bB = min(B, 128)
    while vmem_estimate_dw(bB, pt, qt, k) > vmem_budget and bB > 8:
        bB //= 2
    return bB


def choose_blocks_dw(B: int, p: int, q: int, k: int,
                     vmem_budget: int = 8 * 1024 * 1024
                     ) -> Tuple[int, int, int]:
    """Pick (bB, pt, qt) tiles for the transposed-geometry dw kernel.

    Same constraints as :func:`choose_blocks` with the roles permuted:
    (pt, qt) tile the OUTPUT block grid, bB tiles the batch contraction.
    """
    unit = max(1, 128 // k)
    pt = min(p, max(unit, 8 * unit))
    qt = min(q, max(unit, 8 * unit))
    bB = choose_batch_block_dw(B, pt, qt, k, vmem_budget)
    while vmem_estimate_dw(bB, pt, qt, k) > vmem_budget and pt > unit:
        pt = max(unit, pt // 2)
    while vmem_estimate_dw(bB, pt, qt, k) > vmem_budget and qt > unit:
        qt = max(unit, qt // 2)
    return bB, pt, qt


def choose_blocks(B: int, p: int, q: int, k: int,
                  vmem_budget: int = 8 * 1024 * 1024) -> Tuple[int, int, int]:
    """Pick (bB, pt, qt) tile sizes.

    Constraints:
      * lane dim of the x tile (qt·k) and y tile (pt·k) should be a multiple
        of 128 where the problem allows (MXU/VREG alignment);
      * VMEM working set (x tile + w tiles + scratch + y tile) under budget.
    """
    # lane-align the block counts for small k
    unit = max(1, 128 // k)
    qt = min(q, max(unit, 8 * unit))
    pt = min(p, max(unit, 8 * unit))
    bB = choose_batch_block(B, pt, qt, k, vmem_budget)
    while vmem_estimate(bB, pt, qt, k) > vmem_budget and pt > unit:
        pt = max(unit, pt // 2)
    while vmem_estimate(bB, pt, qt, k) > vmem_budget and qt > unit:
        qt = max(unit, qt // 2)
    return bB, pt, qt


def _bc_kernel(x_ref, wr_ref, wi_ref, c_ref, s_ref, ci_ref, si_ref,
               *refs, k: int, nq: int, out_dtype, activation: str = "none",
               has_bias: bool = False, has_scale: bool = False):
    """One (b, i, j) grid step. Shapes (per tile):
      x_ref  : (bB, qt·k)      wr/wi : (pt, qt, K) f32 — or int8 w/ has_scale
      c/s    : (k, K)          ci/si : (K, k)
      sc_ref : (pt, qt, 1)     [only when has_scale — f32 per-block scales]
      b_ref  : (1, pt·k)       [only when has_bias]
      o_ref  : (bB, pt·k)      yr/yi : (bB, pt, K) f32 scratch

    Quantized tables (``has_scale``): wr/wi stream HBM→VMEM as int8 (4× the
    effective weight bandwidth of the fp32 path) and dequantize HERE, on the
    VMEM tile, immediately before the per-bin complex GEMM — one broadcast
    multiply by the (pt, qt, 1) scale tile, the analogue of the MSR
    bit-truncation decode sitting between BRAM and the FPGA multiplier
    array. The scale is shared across the K bins and the re/im pair, so the
    dequant is exactly ``quant.dequantize_symmetric`` and the kernel output
    is bit-identical to running the fp32 kernel on host-dequantized tables.

    The fused epilogue (bias add + activation) runs on the final q step,
    after the inverse rDFT and before the VMEM→HBM writeback — mirroring the
    paper's IFFT + bias/activation peripheral stage.
    """
    refs = list(refs)
    sc_ref = refs.pop(0) if has_scale else None
    b_ref = refs.pop(0) if has_bias else None
    o_ref, yr_acc, yi_acc = refs
    j = pl.program_id(2)
    K = k // 2 + 1
    bB = x_ref.shape[0]
    pt, qt = wr_ref.shape[:2]

    @pl.when(j == 0)
    def _zero():
        yr_acc[...] = jnp.zeros_like(yr_acc)
        yi_acc[...] = jnp.zeros_like(yi_acc)

    xb = x_ref[...].astype(jnp.float32).reshape(bB * qt, k)
    # forward rDFT on the MXU: (bB·qt, k) @ (k, K)
    xr = (xb @ c_ref[...]).reshape(bB, qt, K)
    xi = (xb @ s_ref[...]).reshape(bB, qt, K)
    wr = wr_ref[...]
    wi = wi_ref[...]
    if has_scale:
        # in-tile dequant: int8 -> f32 is exact, then one broadcast multiply
        sc = sc_ref[...]
        wr = wr.astype(jnp.float32) * sc
        wi = wi.astype(jnp.float32) * sc
    # per-bin complex GEMM: contract q, batch f  (bqf,pqf->bpf)
    dn = (((1,), (1,)), ((2,), (2,)))   # contracting q; batching f
    def dot(a, b):
        # a (bB, qt, K), b (pt, qt, K) -> (K, bB, pt) -> (bB, pt, K)
        r = jax.lax.dot_general(a, b, dimension_numbers=dn,
                                preferred_element_type=jnp.float32)
        return jnp.transpose(r, (1, 2, 0))
    yr_acc[...] += dot(xr, wr) - dot(xi, wi)
    yi_acc[...] += dot(xr, wi) + dot(xi, wr)

    @pl.when(j == nq - 1)
    def _finish():
        yr = yr_acc[...].reshape(bB * pt, K)
        yi = yi_acc[...].reshape(bB * pt, K)
        # inverse rDFT on the MXU: (bB·pt, K) @ (K, k)
        # (bB, pt·k) lane-flat, or (bB, pt, k) when 128 ∤ k (see
        # bc_matmul_pallas); the bias block has the matching layout
        y = (yr @ ci_ref[...] + yi @ si_ref[...]).reshape(o_ref.shape)
        if has_bias:
            y = y + b_ref[...].astype(jnp.float32)
        y = apply_activation(y, activation)
        o_ref[...] = y.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_b", "block_p", "block_q", "interpret",
                     "activation"),
)
def bc_matmul_pallas(
    x: jax.Array,
    wr: jax.Array,
    wi: jax.Array,
    c: jax.Array,
    s: jax.Array,
    ci: jax.Array,
    si: jax.Array,
    bias: Optional[jax.Array] = None,
    w_scale: Optional[jax.Array] = None,
    *,
    k: int,
    block_b: int,
    block_p: int,
    block_q: int,
    interpret: bool = False,
    activation: str = "none",
) -> jax.Array:
    """x (B, q·k) × freq-weights (p, q, K)·2 -> y (B, p·k).

    ``bias`` (1, p·k) and ``activation`` run inside the kernel's final-q
    epilogue (fused, no extra HBM round-trip). With ``w_scale`` (p, q, 1)
    f32, wr/wi are int8 tables dequantized in-kernel on the VMEM tile (see
    ``_bc_kernel``); the scale tile rides the same (i, j) index map as the
    weight tiles. The trailing unit axis keeps its block legal for any q:
    a (pt, qt) block of a (p, q) array would need qt % 128 == 0 or
    qt == q. Caller (ops.py / plan.py) guarantees B % block_b == 0,
    p % block_p == 0, q % block_q == 0 (it pads otherwise).
    """
    B = x.shape[0]
    p, q, K = wr.shape
    assert K == k // 2 + 1
    grid = (B // block_b, p // block_p, q // block_q)
    # Mosaic casts (bB, qt·k) <-> (bB·qt, k) only when k fills whole
    # 128-lane rows. Smaller blocks stream x, y and the bias with the block
    # on its own axis, so the in-kernel casts only merge or split sublanes.
    blocked = k % 128 != 0
    if blocked:
        x = x.reshape(B, q, k)
        x_spec = pl.BlockSpec((block_b, block_q, k), lambda b, i, j: (b, j, 0))
        y_spec = pl.BlockSpec((block_b, block_p, k), lambda b, i, j: (b, i, 0))
        y_shape = (B, p, k)
    else:
        x_spec = pl.BlockSpec((block_b, block_q * k), lambda b, i, j: (b, j))
        y_spec = pl.BlockSpec((block_b, block_p * k), lambda b, i, j: (b, i))
        y_shape = (B, p * k)

    has_bias = bias is not None
    has_scale = w_scale is not None
    kernel = functools.partial(
        _bc_kernel, k=k, nq=grid[2], out_dtype=x.dtype,
        activation=activation, has_bias=has_bias, has_scale=has_scale,
    )
    in_specs = [
        x_spec,
        pl.BlockSpec((block_p, block_q, K), lambda b, i, j: (i, j, 0)),
        pl.BlockSpec((block_p, block_q, K), lambda b, i, j: (i, j, 0)),
        pl.BlockSpec((k, K), lambda b, i, j: (0, 0)),
        pl.BlockSpec((k, K), lambda b, i, j: (0, 0)),
        pl.BlockSpec((K, k), lambda b, i, j: (0, 0)),
        pl.BlockSpec((K, k), lambda b, i, j: (0, 0)),
    ]
    args = [x, wr, wi, c, s, ci, si]
    if has_scale:
        in_specs.append(
            pl.BlockSpec((block_p, block_q, 1), lambda b, i, j: (i, j, 0))
        )
        args.append(w_scale)
    if has_bias:
        if blocked:
            bias = bias.reshape(1, p, k)
            b_spec = pl.BlockSpec((1, block_p, k), lambda b, i, j: (0, i, 0))
        else:
            b_spec = pl.BlockSpec((1, block_p * k), lambda b, i, j: (0, i))
        in_specs.append(b_spec)
        args.append(bias)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct(y_shape, x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_b, block_p, K), jnp.float32),
            pltpu.VMEM((block_b, block_p, K), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return y.reshape(B, p * k)


# ---------------------------------------------------------------------------
# Transposed-geometry weight adjoint: dL/dw as a per-bin complex GEMM with
# the train batch promoted to the contraction axis
# ---------------------------------------------------------------------------


def _bc_dw_kernel(x_ref, g_ref, c_ref, s_ref, cit_ref, sit_ref, ct_ref,
                  st_ref, *refs, k: int, nb: int, freq_out: bool):
    """One (i, j, b) grid step of the dw kernel. Shapes (per tile):
      x_ref   : (bB, qt·k)     g_ref : (bB, pt·k)
      c/s     : (k, K)         cit/sit : (k, K)      ct/st : (K, k)
      o_ref   : (pt, qt·k)             [freq_out=False — time-domain dw]
      dwr/dwi : (pt, qt, K)            [freq_out=True  — frozen-param path]
      r/i acc : (pt, qt, K) f32 scratch

    ``dŵ[p,q,f] = Σ_b ĝ[b,p,f]·conj(x̂[b,q,f])`` — the forward kernel's
    per-bin GEMM with batch as the contraction axis: g transforms through
    the adjoint of the inverse rDFT (Ciᵀ/Siᵀ), x through the analysis
    bases (C/S), conj(x̂) negates the imaginary part. The epilogue on the
    final batch step either folds the cotangent back to the time domain
    (dw = dwr@Cᵀ + dwi@Sᵀ) or writes the (dwr, dwi) pair raw.
    """
    if freq_out:
        dwr_ref, dwi_ref, r_acc, i_acc = refs
    else:
        o_ref, r_acc, i_acc = refs
    b = pl.program_id(2)
    K = k // 2 + 1
    bB = x_ref.shape[0]
    pt, qt = r_acc.shape[:2]

    @pl.when(b == 0)
    def _zero():
        r_acc[...] = jnp.zeros_like(r_acc)
        i_acc[...] = jnp.zeros_like(i_acc)

    xb = x_ref[...].astype(jnp.float32).reshape(bB * qt, k)
    xr = (xb @ c_ref[...]).reshape(bB, qt, K)
    xi = (xb @ s_ref[...]).reshape(bB, qt, K)
    gb = g_ref[...].astype(jnp.float32).reshape(bB * pt, k)
    # adjoint of the inverse rDFT on the MXU: gyr = g @ Ciᵀ, gyi = g @ Siᵀ
    gyr = (gb @ cit_ref[...]).reshape(bB, pt, K)
    gyi = (gb @ sit_ref[...]).reshape(bB, pt, K)
    # per-bin complex GEMM, batch contracted: dŵ[p,q,f] += ĝ[b,p,f]·x̂*[b,q,f]
    dn = (((0,), (0,)), ((2,), (2,)))   # contracting b; batching f

    def dot(a, c):
        # a (bB, pt, K), c (bB, qt, K) -> (K, pt, qt) -> (pt, qt, K)
        r = jax.lax.dot_general(a, c, dimension_numbers=dn,
                                preferred_element_type=jnp.float32)
        return jnp.transpose(r, (1, 2, 0))

    r_acc[...] += dot(gyr, xr) + dot(gyi, xi)
    i_acc[...] += dot(gyi, xr) - dot(gyr, xi)

    @pl.when(b == nb - 1)
    def _finish():
        if freq_out:
            dwr_ref[...] = r_acc[...]
            dwi_ref[...] = i_acc[...]
        else:
            dwr = r_acc[...].reshape(pt * qt, K)
            dwi = i_acc[...].reshape(pt * qt, K)
            # adjoint of the forward rDFT: dw = dwr@Cᵀ + dwi@Sᵀ
            o_ref[...] = (dwr @ ct_ref[...] + dwi @ st_ref[...]).reshape(
                o_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_b", "block_p", "block_q", "freq_out",
                     "interpret"),
)
def bc_dw_pallas(
    x: jax.Array,
    g: jax.Array,
    c: jax.Array,
    s: jax.Array,
    cit: jax.Array,
    sit: jax.Array,
    ct: jax.Array,
    st: jax.Array,
    *,
    k: int,
    block_b: int,
    block_p: int,
    block_q: int,
    freq_out: bool = False,
    interpret: bool = False,
):
    """x (B, Q·k) and upstream cotangent g (B, P·k) -> weight adjoint.

    ``freq_out=False`` returns the time-domain dw (P, Q·k) f32 (`_bwd`,
    trainable block tables); ``freq_out=True`` returns the frequency
    cotangent pair ``(dwr, dwi)`` each (P, Q, K) f32 (`_freq_bwd`, frozen
    frequency parameters). Basis args come from
    ``circulant.dft_bases_adjoint(k)``. Caller (ops.py) guarantees
    B % block_b == 0, P % block_p == 0, Q % block_q == 0 (it pads
    otherwise; zero-padded rows/cols contribute exact zeros).
    """
    B = x.shape[0]
    Q = x.shape[1] // k
    P = g.shape[1] // k
    K = k // 2 + 1
    grid = (P // block_p, Q // block_q, B // block_b)

    kernel = functools.partial(_bc_dw_kernel, k=k, nb=grid[2],
                               freq_out=freq_out)
    # same layout rule as bc_matmul_pallas: unless k fills whole 128-lane
    # rows, the block gets its own axis so the in-kernel casts stay on
    # sublanes
    blocked = k % 128 != 0
    if blocked:
        x = x.reshape(B, Q, k)
        g = g.reshape(B, P, k)
        x_spec = pl.BlockSpec((block_b, block_q, k), lambda i, j, b: (b, j, 0))
        g_spec = pl.BlockSpec((block_b, block_p, k), lambda i, j, b: (b, i, 0))
    else:
        x_spec = pl.BlockSpec((block_b, block_q * k), lambda i, j, b: (b, j))
        g_spec = pl.BlockSpec((block_b, block_p * k), lambda i, j, b: (b, i))
    in_specs = [
        x_spec,
        g_spec,
        pl.BlockSpec((k, K), lambda i, j, b: (0, 0)),
        pl.BlockSpec((k, K), lambda i, j, b: (0, 0)),
        pl.BlockSpec((k, K), lambda i, j, b: (0, 0)),
        pl.BlockSpec((k, K), lambda i, j, b: (0, 0)),
        pl.BlockSpec((K, k), lambda i, j, b: (0, 0)),
        pl.BlockSpec((K, k), lambda i, j, b: (0, 0)),
    ]
    if freq_out:
        out_specs = (
            pl.BlockSpec((block_p, block_q, K), lambda i, j, b: (i, j, 0)),
            pl.BlockSpec((block_p, block_q, K), lambda i, j, b: (i, j, 0)),
        )
        out_shape = (
            jax.ShapeDtypeStruct((P, Q, K), jnp.float32),
            jax.ShapeDtypeStruct((P, Q, K), jnp.float32),
        )
    elif blocked:
        out_specs = pl.BlockSpec((block_p, block_q, k),
                                 lambda i, j, b: (i, j, 0))
        out_shape = jax.ShapeDtypeStruct((P, Q, k), jnp.float32)
    else:
        out_specs = pl.BlockSpec((block_p, block_q * k),
                                 lambda i, j, b: (i, j))
        out_shape = jax.ShapeDtypeStruct((P, Q * k), jnp.float32)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_p, block_q, K), jnp.float32),
            pltpu.VMEM((block_p, block_q, K), jnp.float32),
        ],
        interpret=interpret,
    )(x, g, c, s, cit, sit, ct, st)
    return out if freq_out else out.reshape(P, Q * k)
