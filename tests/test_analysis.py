"""The trace auditor audits itself: walker recursion, purity taint,
seeded-violation fixtures per rule (each must FAIL with a source-located
diagnostic), the AST lint on synthetic files, and the CLI report schema.

The seeded fixtures are the auditor's own regression floor: a rule that
stops firing on the violation it exists to catch would silently turn the
CI gate green, so every rule here is driven over both a conforming and a
deliberately broken program.
"""

import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (Contract, DenseFallbackDot, DonatedInputsAliased,
                            LaunchBudget, NoDenseDotGeneral, NoFFT,
                            NoWeightConcat, NoWeightFFT, QuantizedTableDtypes,
                            ScopedContractions, StructuralContractError,
                            collect_pure_vars, iter_eqns, iter_scoped_eqns,
                            run_contract, source_location)
from repro.analysis.lint import ALLOW_BROAD_EXCEPT_MARKER, lint_file
from repro.kernels.block_circulant import build_plan
from repro.kernels.block_circulant.ops import (count_pallas_launches,
                                               outer_dot_shapes)

jax.config.update("jax_platform_name", "cpu")


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# ---------------------------------------------------------------------------
# Walker: recursion into higher-order primitives
# ---------------------------------------------------------------------------


def test_walker_counts_launch_inside_scan():
    """The regression the old hand-rolled visit loops missed: a pallas_call
    nested under lax.scan (and under jit) must be seen by the walker —
    and therefore by count_pallas_launches/outer_dot_shapes."""
    plan = build_plan(_rand((3, 3, 8)))          # square: scan-carry shaped
    x0 = _rand((4, 24), seed=1)

    def scanned(x):
        def body(carry, _):
            return plan.apply(carry) * 0 + carry, ()
        y, _ = jax.lax.scan(body, x, jnp.arange(3))
        return y

    jp = jax.make_jaxpr(jax.jit(scanned))(x0)
    # the launch sits two levels down: pjit -> scan -> pallas_call
    assert count_pallas_launches(jp) == 1
    assert LaunchBudget(exact=1).check(jp) == []


def test_walker_counts_dot_inside_cond_branches():
    def f(x, w):
        return jax.lax.cond(x.sum() > 0,
                            lambda: x @ w,
                            lambda: (x * 2.0) @ w)

    jp = jax.make_jaxpr(f)(_rand((3, 4)), _rand((4, 5), seed=1))
    dots = [e for e in iter_eqns(jp) if e.primitive.name == "dot_general"]
    assert len(dots) == 2                      # one per cond branch
    assert outer_dot_shapes(jp) != []


def test_walker_does_not_descend_into_pallas_bodies():
    """Kernel-internal dots are not "outer" contractions: the launch itself
    is yielded, its VMEM program is not (unless asked)."""
    plan = build_plan(_rand((2, 3, 8)))
    jp = jax.make_jaxpr(plan.apply)(_rand((4, 24), seed=1))
    assert outer_dot_shapes(jp) == []
    outer = [e.primitive.name for e in iter_eqns(jp)]
    inner = [e.primitive.name for e in iter_eqns(jp, into_pallas=True)]
    assert "pallas_call" in outer
    assert len(inner) > len(outer)             # the body only shows up opted-in


def test_source_location_points_at_user_code():
    jp = jax.make_jaxpr(lambda w: jnp.fft.rfft(w, axis=-1))(_rand((2, 8)))
    (eqn,) = [e for e in iter_eqns(jp) if e.primitive.name == "fft"]
    where = source_location(eqn)
    assert where and "test_analysis.py" in where


# ---------------------------------------------------------------------------
# Purity taint analysis
# ---------------------------------------------------------------------------


def test_purity_separates_weight_from_activation():
    # w and x get different shapes so the tracer does NOT dedup their rfft
    # sub-jaxprs (see test_purity_shared_subjaxpr_meets_impure)
    def f(w, x):
        wf = jnp.fft.rfft(w, axis=-1)           # pure: derives from w only
        xf = jnp.fft.rfft(x, axis=-1)           # impure: derives from x
        return jnp.fft.irfft(wf[:2] * xf, n=8, axis=-1)

    jp = jax.make_jaxpr(f)(_rand((3, 8)), _rand((2, 8), seed=1))
    pure = collect_pure_vars(jp, [True, False])  # w pure, x not
    ffts = [e for e in iter_eqns(jp) if e.primitive.name == "fft"]
    assert len(ffts) == 3
    purities = sorted(e.invars[0] in pure for e in ffts)
    assert purities == [False, False, True]      # only rfft(w) is weight-side
    # NoWeightFFT flags exactly that one, with provenance
    vs = NoWeightFFT(n_param_invars=1).check(jp)
    assert len(vs) == 1 and "test_analysis.py" in vs[0].where


def test_purity_shared_subjaxpr_meets_impure():
    """Same-shape rfft call sites share one traced sub-jaxpr object; its
    inner vars take the meet (AND) of every caller's purity, so sharing
    demotes to impure — conservative (can hide a weight fft at a shared
    call site, never invent one)."""
    def f(w, x):
        return (jnp.fft.rfft(w, axis=-1).real.sum()
                + jnp.fft.rfft(x, axis=-1).real.sum())

    jp = jax.make_jaxpr(f)(_rand((2, 8)), _rand((2, 8), seed=1))
    pure = collect_pure_vars(jp, [True, False])
    inner = [e for e in iter_eqns(jp) if e.primitive.name == "fft"]
    assert all(e.invars[0] not in pure for e in inner)
    assert NoWeightFFT(n_param_invars=1).check(jp) == []


def test_purity_taint_propagates_through_scan():
    """Taint must survive a scan boundary: an fft of a scan carry seeded
    from activations is NOT weight-side."""
    def f(w, x):
        def body(carry, _):
            return carry + w, jnp.fft.rfft(carry, axis=-1).real.sum()
        _, ys = jax.lax.scan(body, x, jnp.arange(2))
        return ys

    jp = jax.make_jaxpr(f)(_rand((2, 8)), _rand((2, 8), seed=1))
    assert NoWeightFFT(n_param_invars=1).check(jp) == []


def test_purity_closed_over_constants_are_pure():
    """A weight baked into the trace as a constant is still weight data —
    the NoWeightFFT fixture a closure would otherwise smuggle past."""
    w = _rand((2, 8))

    def f(x):
        return jnp.fft.rfft(w, axis=-1).real.sum() + x.sum()

    jp = jax.make_jaxpr(f)(_rand((4,), seed=1))
    vs = NoWeightFFT(n_param_invars=0).check(jp)
    assert len(vs) == 1


# ---------------------------------------------------------------------------
# Seeded-violation fixtures: every rule fires on the program it exists for
# ---------------------------------------------------------------------------


def test_no_fft_rule_fires_with_location():
    jp = jax.make_jaxpr(lambda x: jnp.fft.irfft(
        jnp.fft.rfft(x, axis=-1), n=8, axis=-1))(_rand((2, 8)))
    vs = NoFFT().check(jp)
    assert len(vs) == 2
    assert all(v.primitive == "fft" for v in vs)
    assert all(v.where and "test_analysis.py:" in v.where for v in vs)
    assert NoFFT().check(jax.make_jaxpr(lambda x: x * 2)(_rand((2,)))) == []


def test_dense_fallback_rule_fires_only_on_weight_side():
    w = _rand((24, 40), seed=1)

    def fallback(w, x):
        return x @ w                              # the silent dense path

    jp = jax.make_jaxpr(fallback)(w, _rand((4, 24), seed=2))
    vs = DenseFallbackDot([(24, 40)], n_param_invars=1).check(jp)
    assert len(vs) == 1 and vs[0].primitive == "dot_general"
    # same shape as a pure activation contraction: not a fallback
    def act(w, a, b):
        return (a @ b) @ w[:40, :4]

    jp2 = jax.make_jaxpr(act)(w.T, _rand((24, 24), seed=3),
                              _rand((24, 40), seed=4))
    vs2 = DenseFallbackDot([(24, 40)], n_param_invars=1).check(jp2)
    assert all("(24, 40)" not in str(v) or v.primitive != "dot_general"
               for v in vs2) or vs2 == []


def test_scoped_walker_carries_enclosing_scopes_into_sub_jaxprs():
    """A sub-jaxpr's own name stacks start empty: the scopes of the scan
    (and checkpoint) that hold it must be carried down."""
    def body(c, w):
        return c @ w, None

    def f(x, ws):
        with jax.named_scope("outer"):
            y = jax.checkpoint(lambda x: jax.lax.scan(body, x, ws)[0])(x)
        with jax.named_scope("head"):
            return y @ ws[0]

    jp = jax.make_jaxpr(f)(_rand((4, 4)), _rand((3, 4, 4), seed=1))
    dots = [sc for e, sc in iter_scoped_eqns(jp)
            if e.primitive.name == "dot_general"]
    assert sorted(dots) == [("head",), ("outer",)]


def test_scoped_contractions_rule_fires_outside_every_scope():
    w = _rand((8, 8))

    def step(x):
        with jax.named_scope("attention"):
            with jax.named_scope("circulant"):
                x = x @ w
            x = x @ w
        with jax.named_scope("kv_move"):
            x = x[::-1]
        with jax.named_scope("head"):
            x = jnp.einsum("bd,vd->bv", x, w)
        return x @ w                              # the scope a refactor lost

    jp = jax.make_jaxpr(step)(_rand((2, 8), seed=1))
    vs = ScopedContractions().check(jp)
    assert len(vs) == 1 and vs[0].primitive == "dot_general"
    assert vs[0].where and "test_analysis.py:" in vs[0].where
    plan = build_plan(_rand((3, 3, 8)))
    x = _rand((4, 24), seed=2)
    assert len(ScopedContractions().check(
        jax.make_jaxpr(plan.apply)(x))) == 1     # an unscoped pallas_call
    jp2 = jax.make_jaxpr(jax.named_scope("circulant")(plan.apply))(x)
    assert ScopedContractions().check(jp2) == []


def test_decode_surfaces_carry_the_scope_rule():
    """The attention and expert decoders' decode surfaces are held to the
    scopes (the engine audit passes, so every contraction of theirs is
    scoped: the router and experts under ``moe``, latent attention under
    ``attention``); recurrent families have contractions no scope names,
    and are not."""
    from repro.analysis.contracts import (_decode_rules, _smoke_engine,
                                          audit_engine)
    from repro.configs.registry import get_smoke
    from repro.launch.specs import build_model
    from repro.nn.module import init_params

    def engine(arch):
        cfg = get_smoke(arch)
        model = build_model(cfg)
        return _smoke_engine(model, cfg, init_params(model.specs(), 0), "off")

    for arch in ("qwen3-0.6b", "qwen3-moe-235b-a22b", "deepseek-v2-lite"):
        eng = engine(arch)
        assert [type(r) for r in _decode_rules(eng)] == [ScopedContractions]
        assert audit_engine(eng) == []
    assert _decode_rules(engine("rwkv6-7b")) == ()


def test_launch_budget_points_at_excess_launch():
    plan = build_plan(_rand((3, 3, 8)))
    x = _rand((4, 24), seed=1)
    jp = jax.make_jaxpr(lambda x: plan.apply(plan.apply(x) * 0 + x))(x)
    assert LaunchBudget(exact=2).check(jp) == []
    vs = LaunchBudget(exact=1).check(jp)
    assert len(vs) == 1 and vs[0].primitive == "pallas_call"
    assert vs[0].where                            # source-located culprit
    assert LaunchBudget(max_launches=2).check(jp) == []
    with pytest.raises(ValueError):
        LaunchBudget()
    with pytest.raises(ValueError):
        LaunchBudget(exact=1, max_launches=2)


def test_no_weight_concat_distinguishes_sides():
    wa, wb = _rand((4, 3, 8)), _rand((4, 3, 8), seed=1)

    def weight_stack(wa, wb, x):
        return (jnp.concatenate([wa, wb], axis=0) * x).sum()

    def act_stack(wa, wb, x):
        return jnp.concatenate([x, x], axis=0).sum() + (wa + wb).sum()

    x = _rand((8, 3, 8), seed=2)
    jp_w = jax.make_jaxpr(weight_stack)(wa, wb, x)
    jp_a = jax.make_jaxpr(act_stack)(wa, wb, x)
    rule = NoWeightConcat(table_shapes=[(8, 3, 8)], n_param_invars=2)
    vs = rule.check(jp_w)
    assert len(vs) == 1 and vs[0].primitive == "concatenate"
    assert rule.check(jp_a) == []                 # activation concat passes
    # strict mode flags any concatenate at all
    assert len(NoWeightConcat().check(jp_a)) == 1


def test_quantized_dtype_rule_names_the_bad_path():
    good = {"layer": {"wr": jnp.zeros((2, 3, 5), jnp.int8),
                      "wi": jnp.zeros((2, 3, 5), jnp.int8),
                      "w_scale": jnp.ones((2, 3, 1), jnp.float32)}}
    assert QuantizedTableDtypes("int8").check_params(good) == []
    bad = {"layer": {"wr": jnp.zeros((2, 3, 5), jnp.float32),
                     "wi": jnp.zeros((2, 3, 5), jnp.int8),
                     "w_scale": jnp.ones((2, 3, 1), jnp.float16)}}
    vs = QuantizedTableDtypes("int8").check_params(bad)
    msgs = "\n".join(v.message for v in vs)
    assert "layer/wr" in msgs and "layer/w_scale" in msgs
    with pytest.raises(ValueError):
        QuantizedTableDtypes("int4")


def test_donation_rule_reads_lowered_text():
    def f(x):
        return x + 1

    x = jnp.zeros((8,), jnp.float32)
    donated = jax.jit(f, donate_argnums=(0,)).lower(x).as_text()
    plain = jax.jit(f).lower(x).as_text()
    rule = DonatedInputsAliased()
    assert rule.check_lowered(donated) == []
    vs = rule.check_lowered(plain, surface="serve_donation[decode]")
    assert len(vs) == 1 and vs[0].surface == "serve_donation[decode]"


def test_contract_stamps_surface_and_error_formats():
    jp = jax.make_jaxpr(lambda x: jnp.fft.rfft(x, axis=-1))(_rand((2, 8)))
    c = Contract(name="plan_forward[k=8]", rules=(NoFFT(),))
    vs = run_contract(c, jp)
    assert vs and vs[0].surface == "plan_forward[k=8]"
    err = StructuralContractError(vs)
    assert "plan_forward[k=8]" in str(err) and "NoFFT" in str(err)
    assert "test_analysis.py" in str(err)          # provenance in the message
    # violations serialize losslessly for the CLI artifact
    rt = json.loads(json.dumps(vs[0].to_json()))
    assert rt["rule"] == "NoFFT" and rt["surface"] == "plan_forward[k=8]"


# ---------------------------------------------------------------------------
# AST lint on synthetic files
# ---------------------------------------------------------------------------


def _lint_src(tmp_path, rel, src):
    p = tmp_path / rel.replace("/", "__")
    p.write_text(textwrap.dedent(src))
    return lint_file(str(p), rel=rel)


def test_lint_fft_outside_core(tmp_path):
    src = """
        import jax.numpy as jnp
        def f(w):
            return jnp.fft.rfft(w, axis=-1)
    """
    vs = _lint_src(tmp_path, "serve/helper.py", src)
    assert any(v.rule == "fft-outside-core" and ":4" in v.where for v in vs)
    # the blessed locations pass
    assert _lint_src(tmp_path, "core/circulant.py", src) == []
    assert _lint_src(tmp_path, "kernels/block_circulant/opsx.py", src) == []


def test_lint_nondeterminism_and_sync_only_in_serve(tmp_path):
    src = """
        import random, time, jax
        def step(x):
            t0 = time.monotonic()
            if random.random() < 0.5:
                x.block_until_ready()
            return jax.device_get(x), t0
        rng = random.Random(0)          # seeded: allowed
    """
    vs = _lint_src(tmp_path, "serve/engine2.py", src)
    rules = sorted(v.rule for v in vs)
    assert rules == ["blocking-sync-in-serve", "blocking-sync-in-serve",
                     "nondeterminism-in-serve", "nondeterminism-in-serve"]
    # identical code outside serve/ is not this lint's business
    assert _lint_src(tmp_path, "train/loop2.py", src) == []


def test_lint_broad_except_and_marker(tmp_path):
    bad = """
        def f():
            try:
                return 1
            except Exception:
                return 0
    """
    vs = _lint_src(tmp_path, "launch/x.py", bad)
    assert [v.rule for v in vs] == ["broad-except"]
    ok = f"""
        def f():
            try:
                return 1
            # {ALLOW_BROAD_EXCEPT_MARKER} — fixture
            except BaseException:
                return 0
    """
    assert _lint_src(tmp_path, "launch/x.py", ok) == []


def test_lint_reports_syntax_errors_as_violations(tmp_path):
    vs = _lint_src(tmp_path, "serve/broken.py", "def f(:\n")
    assert [v.rule for v in vs] == ["parse-error"]


# ---------------------------------------------------------------------------
# CLI / whole-config audit
# ---------------------------------------------------------------------------


def test_cli_single_config_report(tmp_path, capsys):
    from repro.analysis.__main__ import main

    out = tmp_path / "report.json"
    rc = main(["--config", "qwen3-0.6b", "--no-lint", "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["schema"] == "repro.analysis/v1"
    assert report["violations_total"] == 0
    (entry,) = report["configs"]
    assert entry["arch"] == "qwen3-0.6b" and entry["violations"] == []
    names = " ".join(entry["surfaces"])
    for expect in ("plan_forward", "plan_train_step", "serve_prefill",
                   "serve_decode", "serve_launch_parity"):
        assert expect in names, names
    assert "ok]" in capsys.readouterr().out


def test_cli_lint_only_on_clean_tree(tmp_path):
    from repro.analysis.__main__ import main

    (tmp_path / "m.py").write_text("x = 1\n")
    assert main(["--lint-root", str(tmp_path)]) == 0


def test_cli_exits_nonzero_on_lint_violation(tmp_path):
    from repro.analysis.__main__ import main

    (tmp_path / "m.py").write_text(
        "try:\n    pass\nexcept Exception:\n    pass\n")
    assert main(["--lint-root", str(tmp_path)]) == 1


def test_audit_config_rejects_unknown_arch():
    from repro.analysis.contracts import audit_config

    with pytest.raises(KeyError):
        audit_config("no-such-arch")
