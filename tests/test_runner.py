"""ModelRunner conformance grid: every family served by the engine must be
bucket-shape invariant (bucketed prefill bit-identical to the unbucketed
B=1 loop through the same runner), the decoder family must be bit-identical
to the pre-refactor reference path (``make_prefill_step``/
``make_decode_step``), snapshot/restore must round-trip per runner, and the
capability flags must gate the prefix cache and the wave baseline."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import LayerGroup, LayerSpec, ModelConfig, SWMConfig
from repro.launch.specs import build_model
from repro.nn.module import init_params
from repro.serve.engine import (Request, ServeEngine, WaveEngine,
                                make_decode_step, make_prefill_step)
from repro.serve.guard import flatten_state_tree, unflatten_state_tree
from repro.serve.runner import (DecoderRunner, EncDecRunner, RecurrentRunner,
                                make_runner, recurrent_mixer_names)

jax.config.update("jax_platform_name", "cpu")

_BASE = dict(name="rt", d_model=32, n_heads=2, n_kv_heads=1, head_dim=16,
             d_ff=64, vocab=48, remat="none", param_dtype="float32",
             compute_dtype="float32")


def _swm():
    return SWMConfig(block_size=8, impl="dft")


def _cfg_attn():
    return ModelConfig(**_BASE, n_layers=2, swm=_swm())


def _cfg_rwkv():
    return ModelConfig(**_BASE, n_layers=2, rwkv_head_dim=16,
                       rwkv_decay_lora=8, rwkv_mix_lora=8, swm=_swm(),
                       groups=(LayerGroup(layers=(
                           LayerSpec(mixer="rwkv", ffn="dense"),),
                           repeat=2),))


def _cfg_mamba():
    return ModelConfig(**_BASE, n_layers=2, swm=_swm(),
                       groups=(LayerGroup(layers=(
                           LayerSpec(mixer="mamba", ffn="dense"),),
                           repeat=2),))


def _cfg_jamba():
    return ModelConfig(**_BASE, n_layers=4, n_experts=4,
                       n_experts_per_token=2, d_ff_expert=64, swm=_swm(),
                       groups=(LayerGroup(layers=(
                           LayerSpec(mixer="mamba", ffn="dense"),
                           LayerSpec(mixer="attn", ffn="moe"),
                           LayerSpec(mixer="mamba", ffn="dense"),
                           LayerSpec(mixer="attn", ffn="moe"),),
                           repeat=1),))


def _cfg_moe():
    return ModelConfig(**_BASE, n_layers=2, n_experts=8,
                       n_experts_per_token=4, d_ff_expert=64, swm=_swm(),
                       groups=(LayerGroup(layers=(
                           LayerSpec(mixer="attn", ffn="moe"),
                           LayerSpec(mixer="attn", ffn="moe"),),
                           repeat=1),))


def _cfg_mla():
    """Latent attention on every layer, a leading dense layer, then routed
    and shared experts with un-normalised gates (DeepSeek-V2's block)."""
    from repro.configs.base import MLAConfig, YarnConfig

    return ModelConfig(**_BASE, n_layers=3, n_experts=8,
                       n_experts_per_token=3, d_ff_expert=16,
                       dense_residual_ffn=True, d_ff_shared=32,
                       first_dense_layers=1, moe_norm_topk=False,
                       mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                                     qk_rope_head_dim=8, v_head_dim=8),
                       yarn=YarnConfig(factor=4.0, original_max_position=16,
                                       mscale=0.707, mscale_all_dim=0.707),
                       swm=_swm())


def _cfg_encdec():
    return ModelConfig(**{**_BASE, "n_kv_heads": 2}, family="encdec",
                       n_layers=2, n_enc_layers=2, enc_seq=8, swm=_swm())


def _cfg_ring():
    """A 4-entry ``attn_local`` ring beside a full-length layer, repeat-
    stacked: decode past position 4 wraps the ring."""
    return ModelConfig(**_BASE, n_layers=4, sliding_window=4, swm=_swm(),
                       groups=(LayerGroup(layers=(
                           LayerSpec(mixer="attn_local", ffn="dense"),
                           LayerSpec(mixer="attn", ffn="dense"),),
                           repeat=2),))


FAMILY_CFGS = {
    "attn": _cfg_attn,
    "rwkv": _cfg_rwkv,
    "mamba": _cfg_mamba,
    "jamba": _cfg_jamba,
    "moe": _cfg_moe,
    "mla": _cfg_mla,
    "encdec": _cfg_encdec,
    "ring": _cfg_ring,
}

EXPECTED_RUNNER = {
    "attn": DecoderRunner,
    "rwkv": RecurrentRunner,
    "mamba": RecurrentRunner,
    "jamba": RecurrentRunner,
    "moe": DecoderRunner,
    "mla": DecoderRunner,
    "encdec": EncDecRunner,
    "ring": DecoderRunner,
}


def _built(family):
    cfg = FAMILY_CFGS[family]()
    model = build_model(cfg)
    params = init_params(model.specs(), 0)
    return cfg, model, params


def _reqs(cfg, seed=7, lens=(3, 9, 5, 12, 2, 7), max_new=3):
    """Mixed prompt lengths so bucketed admission actually pads."""
    rng = np.random.default_rng(seed)
    out = []
    for L in lens:
        extra = None
        if cfg.family == "encdec":
            extra = rng.standard_normal(
                (cfg.enc_seq, cfg.d_model)).astype(np.float32)
        out.append(Request(
            prompt=rng.integers(1, cfg.vocab, size=L).astype(np.int32),
            max_new=max_new, extra=extra))
    return out


def _b1_oracle(runner, params, reqs, cache_len):
    """Greedy B=1 loop THROUGH the runner: exact prompt length (never a
    bucket), fresh per-request state — the unbucketed ground truth every
    bucketed engine run must match bit for bit."""
    outs = []
    for r in reqs:
        p = np.asarray(r.prompt, np.int32).reshape(-1)
        L = p.shape[0]
        state = runner.init_state(1)
        kw = {}
        if r.extra is not None:
            kw["extra"] = jnp.asarray(r.extra)[None]
        lg, ok, state = runner.prefill(
            params, jnp.asarray(p)[None],
            jnp.asarray(np.arange(L, dtype=np.int32))[None],
            state, jnp.asarray([0], jnp.int32), **kw)
        assert bool(np.asarray(ok)[0])
        cur = int(np.argmax(np.asarray(lg)[0]))
        out, pos = [cur], L
        while len(out) < r.max_new:
            lg, ok, state = runner.decode(
                params, jnp.asarray([[cur]], jnp.int32), state,
                jnp.asarray([pos], jnp.int32), jnp.asarray([0], jnp.int32))
            cur = int(np.argmax(np.asarray(lg)[0]))
            out.append(cur)
            pos += 1
        outs.append(out)
    return outs


# ---------------------------------------------------------------------------
# Conformance: bucketed engine == unbucketed B=1 runner loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["rwkv", "mamba", "jamba", "moe", "mla",
                                    "encdec"])
def test_bucketed_matches_b1(family):
    cfg, model, params = _built(family)
    reqs = _reqs(cfg)
    eng = ServeEngine(model, cfg, params, batch=4, cache_len=32)
    assert isinstance(eng.runner, EXPECTED_RUNNER[family])
    outs = eng.generate(reqs)
    ref = _b1_oracle(make_runner(model, cfg, 32), eng.params, reqs, 32)
    assert outs == ref
    # bucketing must also stay inside the compile budget
    assert eng.prefill_compiles <= eng.max_prefill_variants
    assert eng.decode_compiles <= eng.max_decode_variants


def test_decoder_family_matches_prerefactor_reference():
    """The attention-decoder path must be bit-identical to the untouched
    pre-refactor builders (``make_prefill_step``/``make_decode_step``) —
    the refactor's correctness oracle."""
    cfg, model, params = _built("attn")
    reqs = _reqs(cfg)
    eng = ServeEngine(model, cfg, params, batch=4, cache_len=32)
    assert isinstance(eng.runner, DecoderRunner)
    outs = eng.generate(reqs)

    prefill = jax.jit(make_prefill_step(model, cfg))
    decode = jax.jit(make_decode_step(model, cfg))
    ref = []
    for r in reqs:
        p = np.asarray(r.prompt, np.int32).reshape(-1)
        cache = model.init_cache(1, 32)
        logits, cache = prefill(eng.params, jnp.asarray(p)[None], cache)
        cur = int(np.argmax(np.asarray(logits)[0]))
        out, pos = [cur], len(p)
        while len(out) < r.max_new:
            logits, cache = decode(eng.params,
                                   jnp.asarray([[cur]], np.int32), cache,
                                   jnp.asarray([pos], np.int32))
            cur = int(np.argmax(np.asarray(logits)[0]))
            out.append(cur)
            pos += 1
        ref.append(out)
    assert outs == ref


# ---------------------------------------------------------------------------
# In-place decode on the slot pool == gather -> decode_step -> place
# ---------------------------------------------------------------------------

#: (slot rows launched, rows prefilled) over a 6-slot pool. "permuted":
#: four of six live rows, out of order; "pad_lanes": three live rows and
#: a pad lane on a free (never prefilled) row, as the engine borrows them.
_LAUNCHES = {
    "permuted": ([4, 1, 5, 0], [0, 1, 2, 3, 4, 5]),
    "pad_lanes": ([2, 0, 3, 5], [0, 1, 2, 3]),
}


@pytest.mark.parametrize("case", sorted(_LAUNCHES))
@pytest.mark.parametrize("family", [f for f in FAMILY_CFGS if f != "encdec"])
def test_decode_in_place_matches_gather_place(family, case):
    """``runner.decode`` on the whole pool returns the same logits, ``ok``
    and whole pool, bit for bit, as gathering the launched rows, running
    ``model.decode_step`` on them and scattering them back — across
    repeat-stacked and plain groups, recurrent state, and a ring that
    wraps (prompts up to 9 tokens, then 5 steps, on a 4-entry ring)."""
    from repro.kernels.block_circulant.plan import freeze_params

    cfg, model, params = _built(family)
    runner = make_runner(model, cfg, 16)
    assert isinstance(runner, EXPECTED_RUNNER[family])
    params = freeze_params(runner.specs(), params)
    idx_list, filled = _LAUNCHES[case]
    n_slots, Sb = 6, 10
    lens = np.array([3, 7, 5, 9, 2, 8])[:len(filled)]
    rng = np.random.default_rng(3)
    toks = np.zeros((len(filled), Sb), np.int32)
    posn = np.full((len(filled), Sb), -1, np.int32)
    for j, L in enumerate(lens):
        toks[j, Sb - L:] = rng.integers(1, cfg.vocab, size=L)
        posn[j, Sb - L:] = np.arange(L)
    _, _, pool = jax.jit(runner.prefill)(
        params, jnp.asarray(toks), jnp.asarray(posn),
        runner.init_state(n_slots), jnp.asarray(filled, jnp.int32))

    def reference(params, tokens, state, pos, slot_idx):
        sub = runner.gather_state(state, slot_idx)
        logits, sub = model.decode_step(params, tokens, sub, pos,
                                        moe_no_drop=True)
        ok = jnp.isfinite(logits).all(axis=-1)
        return logits, ok, runner.place_state(state, sub, slot_idx)

    in_place, ref = jax.jit(runner.decode), jax.jit(reference)
    idx = jnp.asarray(idx_list, jnp.int32)
    row_len = dict(zip(filled, lens))
    pos = np.array([row_len.get(s, 0) for s in idx_list], np.int32)
    cur = rng.integers(1, cfg.vocab, size=(len(idx_list), 1)).astype(np.int32)
    pool_a = pool_b = pool
    for _ in range(5):
        args = (jnp.asarray(cur), jnp.asarray(pos), idx)
        la, oka, pool_a = in_place(params, args[0], pool_a, *args[1:])
        lb, okb, pool_b = ref(params, args[0], pool_b, *args[1:])
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        np.testing.assert_array_equal(np.asarray(oka), np.asarray(okb))
        for a, b in zip(jax.tree.leaves(pool_a), jax.tree.leaves(pool_b)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert bool(np.asarray(oka).all())
        cur = np.argmax(np.asarray(la), axis=-1).astype(np.int32)[:, None]
        pos = pos + 1


# ---------------------------------------------------------------------------
# Snapshot / restore round-trips per runner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["attn", "rwkv", "encdec"])
def test_snapshot_restore_roundtrip(family, tmp_path):
    cfg, model, params = _built(family)
    reqs = _reqs(cfg, lens=(4, 7, 3))
    d = str(tmp_path)
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                      snapshot_dir=d)
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()
    eng.snapshot()
    fresh = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                        snapshot_dir=d)
    fresh.restore()
    assert eng.drain() == fresh.drain()


def test_snapshot_rejects_other_family(tmp_path):
    """A snapshot taken by one family must not restore into another: the
    fingerprint names the runner, and the opaque state tree leaf count is
    checked against the restoring runner's template."""
    cfg_a, model_a, params_a = _built("attn")
    eng = ServeEngine(model_a, cfg_a, params_a, batch=2, cache_len=32,
                      snapshot_dir=str(tmp_path))
    eng.snapshot()
    cfg_r, model_r, params_r = _built("rwkv")
    other = ServeEngine(model_r, cfg_r, params_r, batch=2, cache_len=32,
                        snapshot_dir=str(tmp_path))
    with pytest.raises(ValueError, match="fingerprint"):
        other.restore()


def test_state_tree_flatten_roundtrip():
    """The generic serialization helpers must round-trip every family's
    state tree bit for bit (canonical leaf order, dtype cast through the
    template)."""
    for family in ("attn", "rwkv", "jamba", "encdec"):
        cfg, model, params = _built(family)
        runner = make_runner(model, cfg, 16)
        state = runner.init_state(2)
        flat = flatten_state_tree(state)
        rebuilt = unflatten_state_tree(runner.init_state(2), flat)
        a = jax.tree_util.tree_leaves(state)
        b = jax.tree_util.tree_leaves(rebuilt)
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    with pytest.raises(ValueError, match="leaves"):
        unflatten_state_tree(runner.init_state(2), {"s00000": np.zeros(3)})


# ---------------------------------------------------------------------------
# Capability flags: prefix cache, extra conditioning, wave guards
# ---------------------------------------------------------------------------


def test_prefix_cache_gated_on_capability():
    cfg, model, params = _built("rwkv")
    with pytest.raises(ValueError, match="recurrent state"):
        ServeEngine(model, cfg, params, batch=2, cache_len=32,
                    prefix_cache=True)
    cfg_e, model_e, params_e = _built("encdec")
    with pytest.raises(ValueError, match="prefix_cache"):
        ServeEngine(model_e, cfg_e, params_e, batch=2, cache_len=32,
                    prefix_cache=True)
    cfg_m, model_m, params_m = _built("mla")
    with pytest.raises(ValueError, match="'mla' layers"):
        ServeEngine(model_m, cfg_m, params_m, batch=2, cache_len=32,
                    prefix_cache=True)


def test_prefix_index_inert_without_capability():
    """Regression: the index/matcher must be no-ops for runners whose
    state has no per-position rows, even if called directly — a recurrent
    donor entry would promise a row copy the runner cannot make."""
    cfg, model, params = _built("rwkv")
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    prompt = np.arange(1, 17, dtype=np.int32)
    eng._index_insert(0, prompt)
    assert len(eng._prefix_index) == 0
    assert eng._slot_prompt[0] is None
    assert eng._match_prefix(prompt) == (None, 0)


def test_decoder_extra_rejected():
    cfg, model, params = _built("attn")
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    bad = Request(prompt=np.arange(1, 5, dtype=np.int32), max_new=2,
                  extra=np.zeros((4, 4), np.float32))
    with pytest.raises(ValueError, match="extra"):
        eng.generate([bad])


def test_encdec_request_validation():
    cfg, model, params = _built("encdec")
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    with pytest.raises(ValueError, match="encoder frames"):
        eng.generate([Request(prompt=np.arange(1, 5, dtype=np.int32),
                              max_new=2)])
    with pytest.raises(ValueError, match="shape"):
        eng.generate([Request(prompt=np.arange(1, 5, dtype=np.int32),
                              max_new=2,
                              extra=np.zeros((3, 3), np.float32))])


def test_wave_engine_guards():
    cfg, model, params = _built("encdec")
    with pytest.raises(ValueError, match="decoder-LM baseline"):
        WaveEngine(model, cfg, params, batch=1, cache_len=32)
    cfg_m, model_m, params_m = _built("mamba")
    with pytest.raises(ValueError, match="recurrent state"):
        WaveEngine(model_m, cfg_m, params_m, batch=2, cache_len=32)
    WaveEngine(model_m, cfg_m, params_m, batch=1, cache_len=32)


def test_recurrent_mixer_names():
    assert recurrent_mixer_names(_cfg_attn()) == ()
    assert recurrent_mixer_names(_cfg_rwkv()) == ("rwkv",)
    assert recurrent_mixer_names(_cfg_jamba()) == ("mamba",)
    assert recurrent_mixer_names(_cfg_encdec()) == ()
