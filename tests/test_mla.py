"""DeepSeek-V2's mechanisms on the CPU at smoke size: latent attention
(expanded prefill, absorbed decode through the cache), YaRN rotary
frequencies, un-normalised top-k gates, the chunked serving dispatch, and
the whole model against the benchmark's plain float32 reference
(``bench/reference_mla.py``) on seeded random weights, logits against
logits."""

import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SWMConfig, YarnConfig
from repro.configs.registry import get_config, get_smoke
from repro.kernels.block_circulant.plan import freeze_params
from repro.kernels.latent_attention import (blocks_needed,
                                            latent_decode_attention)
from repro.launch.specs import build_model
from repro.nn import attention as attn_mod
from repro.nn import moe as moe_mod
from repro.nn.attention import MLAttention
from repro.nn.layers import rope_frequencies, rotary, yarn_mscale
from repro.serve.runner import DecoderRunner

jax.config.update("jax_platform_name", "cpu")

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))
import reference_mla  # noqa: E402
from weights import make_weights  # noqa: E402

SEED = 2**33 + 5


def _smoke(**kw):
    return dataclasses.replace(get_smoke("deepseek-v2-lite"), **kw)


def ref_config(cfg) -> dict:
    """The published ``config.json`` keys the reference reads, for a
    program config."""
    m, y = cfg.mla, cfg.yarn
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "rms_norm_eps": 1e-6, "rope_theta": cfg.rope_theta,
        "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
        "rope_scaling": {"factor": y.factor,
                         "original_max_position_embeddings":
                             y.original_max_position,
                         "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim},
        "n_routed_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.n_experts_per_token,
        "norm_topk_prob": cfg.moe_norm_topk,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "tie_word_embeddings": cfg.tie_embeddings,
    }


def _ref_logits(cfg, w, toks):
    rc = ref_config(cfg)
    h = reference_mla._jit_hidden(json.dumps(rc, sort_keys=True), "f32")(
        w, jnp.asarray(toks))
    return np.asarray(jnp.einsum("bsd,vd->bsv", h,
                                 w["lm_head"]["w"].astype(jnp.float32).T,
                                 precision=jax.lax.Precision.HIGHEST))


def _served_logits(cfg, w, toks, n_prompt, cache_len=32, slots=3):
    """Prefill ``toks[:, :n_prompt]`` through the runner into rows 2, 0 of
    a ``slots`` pool (left-padded to 16), then decode the rest one token at
    a time in place; the logits at positions ``n_prompt - 1 ..``."""
    model = build_model(cfg)
    runner = DecoderRunner(model, cfg, cache_len)
    params = freeze_params(runner.specs(), w)
    B, S = toks.shape
    Sb = 16
    tok = np.zeros((B, Sb), np.int32)
    pos = np.full((B, Sb), -1, np.int32)
    tok[:, Sb - n_prompt:] = toks[:, :n_prompt]
    pos[:, Sb - n_prompt:] = np.arange(n_prompt)
    rows = jnp.asarray([2, 0], jnp.int32)[:B]
    last, ok, pool = jax.jit(runner.prefill)(
        params, jnp.asarray(tok), jnp.asarray(pos), runner.init_state(slots),
        rows)
    out = [np.asarray(last)]
    decode = jax.jit(runner.decode)
    for t in range(n_prompt, S):
        lg, ok, pool = decode(params, jnp.asarray(toks[:, t:t + 1]), pool,
                              jnp.full((B,), t, jnp.int32), rows)
        assert bool(np.asarray(ok).all())
        out.append(np.asarray(lg))
    return np.stack(out, 1)


# Tolerance of the served logits against the reference, relative to the
# logits' largest magnitude: both run in float32; they differ by the
# program's FFT circulant products against the reference's dense expanded
# blocks, its absorbed decode against the expanded form, and summation
# order (measured ~1e-6). The same model computed in bfloat16 misses it by
# two orders of magnitude (test below).
F32_RTOL = 1e-4


def test_prefill_and_cached_decode_match_the_reference():
    cfg = _smoke()
    w = make_weights(build_model(cfg).specs(), SEED)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 14), 0,
                                         cfg.vocab), np.int32)
    ref = _ref_logits(cfg, w, toks)[:, 7:]
    got = _served_logits(cfg, w, toks, 8)
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= F32_RTOL * scale


def test_lower_precision_fails_the_tolerance():
    """The comparison can tell: compute in bfloat16 (weights stored as
    before) and the served logits leave the float32 tolerance."""
    cfg = _smoke()
    w = make_weights(build_model(cfg).specs(), SEED)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 14), 0,
                                         cfg.vocab), np.int32)
    ref = _ref_logits(cfg, w, toks)[:, 7:]
    got = _served_logits(dataclasses.replace(cfg, compute_dtype="bfloat16"),
                         w, toks, 8)
    assert np.abs(got - ref).max() > 10 * F32_RTOL * np.abs(ref).max()


@pytest.mark.parametrize("kind", ["circulant", "frozen", "dense"])
def test_absorbed_decode_matches_expanded_attention(kind, monkeypatch):
    """One latent-attention layer: a decode step through the cache (the
    absorbed form, read in several chunks) gives the expanded form's output
    at that position. f32; only summation order differs (atol 1e-5 on
    outputs of order one)."""
    monkeypatch.setattr(attn_mod, "MLA_DECODE_CHUNK", 4)
    swm = SWMConfig(block_size=0 if kind == "dense" else 8, impl="paper")
    cfg = _smoke(swm=swm)
    layer = MLAttention(cfg)
    p = make_weights(layer.specs(), SEED)
    if kind == "frozen":
        p = freeze_params(layer.specs(), p)
    B, S, T = 2, 11, 16
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    full, _ = layer(p, x, pos)
    cache = attn_mod.init_mla_cache(B, T, cfg.mla, jnp.float32)
    _, cache = layer(p, x[:, :S - 1], pos[:, :S - 1], cache=cache)
    out, cache = layer(p, x[:, S - 1:], pos[:, S - 1:], cache=cache)
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, S - 1]), atol=1e-5)
    assert int(cache["pos"][0, S - 1]) == S - 1


# The kernel's reads against a plain float32 masked softmax: a pool of 8
# rows x 16 positions read in blocks of 4, 16 heads, the smoke widths.
KT, KB, KH, KW, KR, KSCALE = 16, 4, 16, 128, 32, 0.3
# Each pool row's last written position: rows 1, tc - 1, tc, tc + 1 and
# T - 1 long, a wrapped ring, and two more (row 6 is free in the launch
# below and holds a finished request's positions).
FILLS = (0, KB - 2, KB - 1, KB, KT - 2, KT + 5, 6, 9)


def _kernel_pool(dtype, stacked):
    """A latent pool whose row ``n`` holds positions up to ``FILLS[n]``
    (the ring's last T of them once wrapped; -1 in slots never written,
    as a prefill leaves a row). Slots in whole blocks past a row's last
    position hold NaN: a read of them would poison the row's output."""
    rng = np.random.default_rng(7)
    L = 3 if stacked else 1
    lat = rng.standard_normal((L, len(FILLS), KT, KW)).astype(np.float32)
    pos = np.full((L, len(FILLS), KT), -1, np.int32)
    for n, f in enumerate(FILLS):
        for p in range(max(0, f - KT + 1), f + 1):
            pos[:, n, p % KT] = p
        if f < KT:
            lat[:, n, -(-(f + 1) // KB) * KB:] = np.nan
    lat, pos = jnp.asarray(lat, dtype), jnp.asarray(pos)
    return (lat, pos) if stacked else (lat[0], pos[0])


def _plain_attention(qa, lat, pos, q_pos):
    """softmax over positions 0 <= pos <= q_pos of scale * qa . lat, in
    float32, of the latents' first KR values: the launched rows' ``lat``
    ``(B, T, W)`` and ``pos`` ``(B, T)``."""
    lat = np.nan_to_num(np.asarray(lat, np.float32))
    s = np.einsum("bhw,btw->bht", np.asarray(qa, np.float32), lat) * KSCALE
    ok = (np.asarray(pos) >= 0) & (np.asarray(pos) <= np.asarray(q_pos)[:,
                                                                  None])
    s = np.where(ok[:, None], s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bht,btr->bhr", w, lat[..., :KR])


@pytest.mark.parametrize("q_pos,n_b", [(-1, 0), (0, 1), (KB - 1, 1),
                                       (KB, 2), (KT - 1, KT // KB),
                                       (KT, KT // KB), (3 * KT + 1, KT // KB)])
def test_blocks_needed(q_pos, n_b):
    """A row reads the blocks that hold positions 0 .. q_pos, and the whole
    ring once it has wrapped."""
    assert int(blocks_needed(jnp.int32(q_pos), KT, KB)) == n_b


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("launch", ["all", "permuted"])
def test_latent_kernel_matches_plain_softmax(dtype, atol, stacked, launch):
    """The kernel over every row of the pool (``slot_idx`` None), or over
    a permuted subset whose last lane is a pad lane borrowing the free row
    6 at its stale position (the engine launches such lanes and drops
    their outputs), against a plain float32 masked softmax over the same
    rows. A repeat-stacked pool is read at each layer of a ``lax.scan``,
    whose counter is traced. float32 caches agree to summation order;
    bfloat16 ones by the probabilities' rounding to the cache dtype before
    the values' product (2^-8 relative on latents of order one). No row's
    output is NaN: blocks past a row's last position are never read."""
    lat, pos = _kernel_pool(dtype, stacked)
    rows = (np.arange(len(FILLS)) if launch == "all"
            else np.asarray([5, 0, 4, 2, 7, 1, 3, 6]))
    q_pos = np.asarray(FILLS)[rows]
    if launch == "permuted":
        q_pos[-1] += 1                  # the pad lane's stale position
    slot_idx = None if launch == "all" else jnp.asarray(rows, jnp.int32)
    L = lat.shape[0] if stacked else 1
    qa = jax.random.normal(jax.random.PRNGKey(8),
                           (L, len(rows), KH, KW)).astype(dtype)

    def attend(qa_l, layer):
        return latent_decode_attention(
            qa_l, lat, pos, jnp.asarray(q_pos, jnp.int32), slot_idx, layer,
            r=KR, scale=KSCALE, block=KB)

    if stacked:
        got = jax.jit(lambda q: jax.lax.scan(
            lambda c, xs: (c, attend(*xs)), 0,
            (q, jnp.arange(L, dtype=jnp.int32)))[1])(qa)
    else:
        got = jax.jit(lambda q: attend(q[0], None))(qa)[None]
    for layer in range(L):
        lat_l = lat[layer] if stacked else lat
        pos_l = pos[layer] if stacked else pos
        want = _plain_attention(qa[layer], lat_l[rows], pos_l[rows], q_pos)
        np.testing.assert_allclose(np.asarray(got[layer]), want, atol=atol)


def test_chunked_no_drop_dispatch_is_row_local(monkeypatch):
    """21 tokens through the serving dispatch in chunks of 4 (the last one
    padded): a token's output is bit for bit the same whatever the other
    tokens of the launch are (nothing is dropped, so no token displaces
    another), and the unchunked dispatch's to float32 rounding: the
    experts' products run at another batch size there, which the CPU
    backend may sum in another order (measured 9e-8 on outputs of order
    one)."""
    cfg = _smoke()
    moe = build_model(cfg)._ffn(cfg.layer_groups()[1].layers[0], ())["moe"]
    p = make_weights(moe.specs(), SEED)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 7, cfg.d_model))
    whole, _ = moe(p, x, no_drop=True)
    monkeypatch.setattr(moe_mod, "NO_DROP_CHUNK", 4)      # 21 -> 6 chunks
    chunked, _ = moe(p, x, no_drop=True)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                               atol=1e-6)
    other = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    keep = (jnp.arange(21).reshape(3, 7) % 5 == 0)[..., None]
    mixed, _ = moe(p, jnp.where(keep, x, other), no_drop=True)
    np.testing.assert_array_equal(np.asarray(mixed)[keep[..., 0]],
                                  np.asarray(chunked)[keep[..., 0]])


@pytest.mark.parametrize("frozen", [False, True])
def test_routed_pairs_match_a_dispatch_that_drops_nothing(frozen):
    """The serving path (each token-expert pair through its own expert's
    tables) against the training dispatch at a capacity of every token
    (``capacity_factor`` E / T, so nothing is dropped), on time-domain and
    on frozen frequency tables: the same sum, to float32 rounding (the
    experts' products run at other batch sizes)."""
    cfg = _smoke()
    moe = build_model(cfg)._ffn(cfg.layer_groups()[1].layers[0], ())["moe"]
    moe = dataclasses.replace(moe, capacity_factor=moe.n_experts / moe.top_k)
    p = make_weights(moe.specs(), SEED)
    if frozen:
        p = freeze_params(moe.specs(), p)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, cfg.d_model))
    served, _ = moe(p, x, no_drop=True)
    dispatched, _ = moe(p, x)
    np.testing.assert_allclose(np.asarray(served), np.asarray(dispatched),
                               atol=1e-6)


@pytest.mark.parametrize("norm,scale", [(False, 1.0), (False, 2.5),
                                        (True, 1.0)])
def test_top_k_gates_by_hand(norm, scale):
    """Four dense experts, top-2, one token whose router logits are
    [2, 1, 0, -1]: the output is p0 f0(x) + p1 f1(x) times the routed
    scale, or over (p0 + p1) when renormalised."""
    d = 4
    moe = moe_mod.MoE(d_model=d, d_ff=d, n_experts=4, top_k=2,
                      norm_topk=norm, routed_scale=scale,
                      swm=SWMConfig(block_size=0), dtype="float32")
    rng = np.random.default_rng(0)
    x = np.zeros(d, np.float32)
    x[0] = 1.0
    router = np.zeros((d, 4), np.float32)
    router[0] = [2, 1, 0, -1]
    ex = {n: rng.standard_normal((4, d, d)).astype(np.float32)
          for n in ("wi", "wu", "wo")}
    p = {"router": {"w": jnp.asarray(router)},
         "experts": {n: {"w": jnp.asarray(v)} for n, v in ex.items()}}
    y, _ = moe(p, jnp.asarray(x)[None, None], no_drop=True)

    def f(e):
        g = x @ ex["wi"][e]
        return ((g / (1 + np.exp(-g))) * (x @ ex["wu"][e])) @ ex["wo"][e]

    pr = np.exp([2.0, 1, 0, -1])
    pr /= pr.sum()
    want = pr[0] * f(0) + pr[1] * f(1)
    want = want / (pr[0] + pr[1]) if norm else want * scale
    np.testing.assert_allclose(np.asarray(y)[0, 0], want, rtol=1e-5)


def test_yarn_frequencies_and_scale_follow_the_formula():
    """``DeepseekV2YarnRotaryEmbedding`` at the published numbers (dim 64,
    base 1e4, factor 40 over 4096, beta 32 / 1, mscale 0.707 / 0.707),
    written out again here in numpy."""
    cfg = get_config("deepseek-v2-lite")
    y, dim, base = cfg.yarn, 64, 10_000.0

    def corr(rot):
        return (dim * math.log(4096 / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    lo, hi = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    assert (lo, hi) == (10, 23)
    extra = base ** (-np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(rope_frequencies(dim, base, y)),
                               want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m)
    assert m == pytest.approx(1.26081, abs=1e-5)
    assert MLAttention(cfg).scale == pytest.approx(192 ** -0.5 * m * m)
    # mscale / mscale_all_dim = 1: cos and sin carry no factor
    c, _ = rotary(jnp.zeros((1, 1), jnp.int32), dim, base, y)
    np.testing.assert_allclose(np.asarray(c), 1.0)
    half = YarnConfig(factor=40.0, original_max_position=4096,
                      mscale=1.0, mscale_all_dim=0.707)
    c, _ = rotary(jnp.zeros((1, 1), jnp.int32), dim, base, half)
    np.testing.assert_allclose(
        np.asarray(c), yarn_mscale(40, 1.0) / m, rtol=1e-6)


def test_published_shapes():
    """The registry entry at the published widths: two layer groups (the
    dense layer 0, then 26 MoE layers in one scan), ~0.54 B stored
    parameters at k = 128, and a 640-wide cached position (576 padded to
    the TPU's 128 lanes)."""
    from repro.launch.specs import count_params

    cfg = get_config("deepseek-v2-lite")
    groups = cfg.layer_groups()
    assert [(g.repeat, g.layers[0].mixer, g.layers[0].ffn)
            for g in groups] == [(1, "mla", "dense"), (26, "mla", "dense+moe")]
    n = count_params(cfg)
    assert 0.53e9 < n["stored"] < 0.55e9 and 15.6e9 < n["dense"] < 15.8e9
    assert attn_mod.mla_cache_width(cfg.mla) == 640
    runner = DecoderRunner(build_model(cfg), cfg, 4096)
    assert not runner.supports_prefix_cache
    assert "mla" in runner.prefix_cache_unsupported_reason
