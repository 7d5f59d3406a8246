"""Latent-attention decode: one query position per launched row against the
rows' cached latents in the slot pool, reading only each row's filled
blocks.

``latent_decode_attention`` is the absorbed decode of
:class:`repro.nn.attention.MLAttention`. The grid runs over the launched
rows. Row ``b`` reads pool row ``slot_idx[b]`` of layer ``layer``, in blocks
of ``block`` positions, and only blocks ``0 .. n_b - 1``: all of them when
the ring has wrapped (``q_pos >= T``), else those up to ``q_pos``. Each
block crosses HBM once, by a double-buffered DMA into VMEM (the next block,
or the next row's first, is in flight while one is consumed), and feeds
both products there under a running softmax:

    s   = scale * qa . lat^T            (H, block), f32 accumulation
    s  += 0 where 0 <= pos <= q_pos, else -2e38
    acc = acc * exp(m - m') + exp(s - m') . lat[:, :r]   (probabilities in
                                                           the cache dtype)

and the row's output is ``acc / den``. A prefill writes a row blank before
its first position, so the blocks past ``q_pos`` of a ring that has not
wrapped hold no position the mask would keep.

The Mosaic kernel runs where the call is lowered for a TPU; lowered for the
CPU, the same kernel runs in the Pallas interpreter. The choice is made by
the lowering platform (``lax.platform_dependent``), so an executable
compiled for a TPU from a CPU process holds the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["latent_decode_attention", "blocks_needed"]

#: The mask's additive bias for a position outside ``[0, q_pos]`` (the
#: value ``nn.attention._mask_bias`` adds).
_NEG = -2.0e38


def blocks_needed(q_pos, cache_len: int, block: int):
    """Blocks of ``block`` positions a row at ``q_pos`` reads: all of the
    ring once it has wrapped, else those holding positions ``0 .. q_pos``
    (none for a negative position)."""
    n = jnp.maximum(q_pos + block, 0) // block
    return jnp.where(q_pos >= cache_len, cache_len // block, n)


def _kernel(slot_ref, qpos_ref, layer_ref,            # scalar prefetch
            qa_ref, lat_hbm, pos_hbm,                   # inputs
            out_ref,                                    # output
            lat_buf, pos_buf, sem, cur_ref,             # scratch
            *, block, r, scale):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    T = lat_hbm.shape[2]
    layer = layer_ref[0]

    def n_blocks(row):
        return blocks_needed(qpos_ref[row], T, block)

    g = pos_buf.shape[1]

    def copies(row, i, buf):
        t0 = pl.multiple_of(i * block, block)
        slot = slot_ref[row]
        return (
            pltpu.make_async_copy(
                lat_hbm.at[layer, slot, pl.ds(t0, block)],
                lat_buf.at[buf], sem.at[0, buf]),
            # the positions of the tile of ``g`` rows that holds the slot:
            # a DMA cannot slice one row out of a tile
            pltpu.make_async_copy(
                pos_hbm.at[layer, pl.ds(slot // g * g, g), pl.ds(t0, block)],
                pos_buf.at[buf], sem.at[1, buf]),
        )

    def start(row, i, buf):
        for c in copies(row, i, buf):
            c.start()

    nb = n_blocks(b)

    # the first row starts its own first block; every later row's was
    # started by the row before it, into buffer ``cur_ref[0]``
    @pl.when(b == 0)
    def _():
        cur_ref[0] = 0

        @pl.when(nb > 0)
        def _():
            start(b, 0, 0)

    cur = cur_ref[0]
    nb_next = jnp.where(b + 1 < n_rows, n_blocks(jnp.minimum(b + 1,
                                                            n_rows - 1)), 0)
    q = qa_ref[0]                                        # (H, W)
    qp = qpos_ref[b]
    row_in_g = slot_ref[b] % g
    H = q.shape[0]

    def body(i, carry):
        m, den, acc = carry
        buf = (cur + i) % 2
        nxt = 1 - buf

        @pl.when(i + 1 < nb)
        def _():
            start(b, i + 1, nxt)

        @pl.when(jnp.logical_and(i + 1 == nb, nb_next > 0))
        def _():
            start(b + 1, 0, nxt)

        for c in copies(b, i, buf):
            c.wait()
        lat = lat_buf[buf]                               # (block, W)
        p_ok = jnp.where(                                # (1, block)
            jax.lax.broadcasted_iota(jnp.int32, (g, block), 0) == row_in_g,
            pos_buf[buf], 0).sum(0, keepdims=True)
        s = jax.lax.dot_general(q, lat, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + jnp.where((p_ok >= 0) & (p_ok <= qp), 0.0, _NEG)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        acc = acc * corr + jax.lax.dot_general(
            pr.astype(lat.dtype), lat[:, :r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, den * corr + pr.sum(-1, keepdims=True), acc

    m, den, acc = jax.lax.fori_loop(0, nb, body, (
        jnp.full((H, 1), -jnp.inf, jnp.float32),
        jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, r), jnp.float32)))

    # a row that read nothing left the next row's first block unstarted
    @pl.when(jnp.logical_and(nb == 0, nb_next > 0))
    def _():
        start(b + 1, 0, cur)

    cur_ref[0] = (cur + nb) % 2
    out_ref[0] = acc / jnp.maximum(den, 1e-30)


def _call(qa, latent, pos, q_pos, slot_idx, layer, *, r, scale, block,
          interpret):
    B, H, W = qa.shape
    N = latent.shape[1]
    g = 8 if N % 8 == 0 else N          # pool rows in one tile of ``pos``
    kernel = functools.partial(_kernel, block=block, r=r, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, r), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, W), latent.dtype),
                pltpu.VMEM((2, g, block), jnp.int32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode_attention",
    )(slot_idx, q_pos, layer, qa, latent, pos)


def latent_decode_attention(qa: jax.Array, latent: jax.Array, pos: jax.Array,
                            q_pos: jax.Array,
                            slot_idx: Optional[jax.Array] = None,
                            layer: Optional[jax.Array] = None, *, r: int,
                            scale: float, block: int) -> jax.Array:
    """Softmax-weighted latents ``(B, H, r)`` in float32.

    ``qa`` ``(B, H, W)`` are the absorbed queries in the cache dtype;
    ``latent`` ``(..., N, T, W)`` and ``pos`` ``(..., N, T)`` a latent cache
    or slot pool, repeat-stacked (a leading axis, read at ``layer``) or
    plain (``layer`` None); ``q_pos`` ``(B,)`` each row's position; row
    ``b`` reads pool row ``slot_idx[b]`` (row ``b`` without it). ``r`` is
    the latent width taken out (the rest of ``W`` is the shared rotary key
    and padding), ``block`` the positions a DMA reads, a divisor of ``T``.
    """
    B = qa.shape[0]
    if layer is None:
        latent, pos = latent[None], pos[None]
        layer = jnp.zeros((1,), jnp.int32)
    else:
        layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    if slot_idx is None:
        slot_idx = jnp.arange(B, dtype=jnp.int32)
    T = latent.shape[2]
    if T % block:
        raise ValueError(f"block {block} must divide the cache length {T}")
    args = (qa, latent, pos, q_pos.astype(jnp.int32),
            slot_idx.astype(jnp.int32), layer)
    kw = dict(r=r, scale=scale, block=block)
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(_call, interpret=True, **kw),
        default=functools.partial(_call, interpret=False, **kw))
