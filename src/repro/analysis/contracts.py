"""Per-surface structural contracts over the repo's traced programs.

A :class:`Contract` names a surface (one traceable executable) and the rule
set it must satisfy; ``audit_config`` instantiates every surface for one
registry config and returns the violations. The surface × rule table:

======================  =====================================================
surface                 rules
======================  =====================================================
plan_forward            NoFFT, NoDenseDotGeneral, LaunchBudget(1),
                        NoWeightConcat (strict) — a fused multi-projection
                        ``BCPlan`` forward at the config's block geometry.
plan_train_step         NoFFT, NoDenseDotGeneral, LaunchBudget(3: forward z
                        + dx + dw), NoWeightConcat (strict) — SGD
                        value_and_grad through the frozen plan.
serve_prefill[...]      NoWeightFFT, DenseFallbackDot, NoWeightConcat
serve_decode[...]       (fused shapes); plus NoFFT when the config's impl is
                        kernel-/DFT-backed (``pallas``/``dft`` — the
                        ``paper``/``freq`` impls legitimately transform
                        *activations*, so only the weight side is
                        contractual); one surface per engine bucket.
                        Decode surfaces of models without recurrent
                        mixers add ScopedContractions (every contraction
                        under a ``DECODE_SCOPES`` name; recurrent mixers
                        have no scope of their own).
serve_params            QuantizedTableDtypes (engine's quantize mode).
serve_donation          DonatedInputsAliased on the lowered decode/prefill
                        modules (engines built with ``donate=True``).
serve_launch_parity     int8 and fp32 engines launch the same number of
                        Pallas kernels per bucket (in-kernel dequant adds
                        no launch) — cross-engine, so it lives in
                        ``audit_config``, not ``ServeEngine.audit``.
======================  =====================================================

``ServeEngine.audit()`` runs the ``serve_*`` single-engine surfaces for a
live engine (``prewarm(audit=True)`` gates compilation on it); the
``python -m repro.analysis`` CLI runs everything for every registry config.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.rules import (DenseFallbackDot, DonatedInputsAliased,
                                  LaunchBudget, NoDenseDotGeneral, NoFFT,
                                  NoWeightConcat, NoWeightFFT,
                                  QuantizedTableDtypes, ScopedContractions,
                                  Violation)

__all__ = [
    "Contract",
    "StructuralContractError",
    "run_contract",
    "circulant_table_shapes",
    "dense_equivalent_shapes",
    "fused_table_shapes",
    "plan_surfaces",
    "audit_engine",
    "audit_config",
]


class StructuralContractError(AssertionError):
    """Raised when an audit gate (prewarm / train-step) finds violations."""

    def __init__(self, violations: Sequence[Violation]):
        self.violations = list(violations)
        lines = "\n".join(f"  - {v}" for v in self.violations)
        super().__init__(
            f"{len(self.violations)} structural contract violation(s):\n"
            f"{lines}"
        )


@dataclasses.dataclass(frozen=True)
class Contract:
    """A named surface and the jaxpr rules that gate it."""

    name: str
    rules: Tuple[Any, ...]


def run_contract(contract: Contract, jaxpr) -> List[Violation]:
    """Apply every rule of ``contract`` to one traced jaxpr; violations come
    back stamped with the surface name."""
    out: List[Violation] = []
    for rule in contract.rules:
        for v in rule.check(jaxpr):
            out.append(dataclasses.replace(v, surface=contract.name))
    return out


# ---------------------------------------------------------------------------
# Shape vocabularies derived from a model's specs / frozen params
# ---------------------------------------------------------------------------


def circulant_table_shapes(specs) -> List[Tuple[int, int, int]]:
    """Per-layer ``(p, q, k)`` time-domain table shapes of every
    circulant-tagged spec (stack/expert lead dims stripped — that is how
    the tables appear inside traced layers)."""
    from repro.nn.module import flatten_with_paths

    shapes = []
    for _, spec in flatten_with_paths(specs):
        if "circulant" in getattr(spec, "tags", ()):
            shapes.append(tuple(int(d) for d in spec.shape[-3:]))
    return sorted(set(shapes))


def dense_equivalent_shapes(specs) -> List[Tuple[int, int]]:
    """``(in, out) = (q*k, p*k)`` dense kernels the circulant layers
    replaced — the shapes a silent dense fallback would contract against.

    Shapes that some *legitimately dense* spec shares (MoE experts, the
    tied logits head, non-SWM projections) are excluded: a same-shaped
    legit contraction is indistinguishable from a fallback by shape alone,
    and a rule that cries wolf gates nothing. The rule therefore covers the
    shapes unique to circulant layers."""
    from repro.nn.module import flatten_with_paths

    legit = set()
    for _, spec in flatten_with_paths(specs):
        if ("circulant" not in getattr(spec, "tags", ())
                and len(spec.shape) >= 2):
            s = tuple(int(d) for d in spec.shape[-2:])
            legit |= {s, s[::-1]}
    return sorted({(q * k, p * k)
                   for (p, q, k) in circulant_table_shapes(specs)
                   if (q * k, p * k) not in legit})


def fused_table_shapes(params) -> List[Tuple[int, ...]]:
    """Shapes of every pre-concatenated fused frozen table in ``params``
    (the ``FUSED_KEY`` stacked ``(sum_p, q, K)`` groups) — the shapes an
    in-trace weight concat would produce."""
    from repro.kernels.block_circulant.plan import FUSED_KEY

    shapes = set()

    def visit(node):
        if isinstance(node, dict):
            fused = node.get(FUSED_KEY)
            if isinstance(fused, dict) and "wr" in fused:
                shapes.add(tuple(int(d) for d in fused["wr"].shape))
            for v in node.values():
                visit(v)
        elif isinstance(node, (tuple, list)):
            for v in node:
                visit(v)

    visit(params)
    return sorted(shapes)


# ---------------------------------------------------------------------------
# Plan surfaces (kernel path at the config's block geometry)
# ---------------------------------------------------------------------------


def _plan_geometry(cfg) -> Tuple[int, int, int]:
    from repro.core import circulant as circ

    d = int(cfg.d_model)
    k = circ.valid_block_size(int(cfg.swm.block_size), d, d)
    if k <= 1:
        raise ValueError(
            f"config {cfg.name!r} admits no circulant block on "
            f"(d_model={d}); plan surfaces need swm enabled")
    return d // k, d // k, k


def plan_surfaces(cfg) -> List[Tuple[Contract, Any]]:
    """(contract, jaxpr) pairs for the frozen-plan kernel path at this
    config's block geometry: a fused 3-projection forward (one launch) and
    an SGD train step through a frozen plan (exactly 3 launches)."""
    from repro.kernels.block_circulant import build_multi_plan, build_plan

    p, q, k = _plan_geometry(cfg)
    key = jax.random.PRNGKey(0)
    scale = (q * k) ** -0.5
    ws = [jax.random.normal(jax.random.fold_in(key, i), (p, q, k),
                            jnp.float32) * scale for i in range(3)]
    x = jax.random.normal(jax.random.fold_in(key, 7), (4, q * k), jnp.float32)

    mp = build_multi_plan(ws)
    fwd_jaxpr = jax.make_jaxpr(mp.apply_multi)(x)
    fwd = Contract(
        name=f"plan_forward[k={k}]",
        rules=(NoFFT(), NoDenseDotGeneral(), LaunchBudget(exact=1),
               NoWeightConcat()),
    )

    plan = build_plan(ws[0])
    y = jax.random.normal(jax.random.fold_in(key, 8), (4, p * k), jnp.float32)
    loss = lambda pl, b: ((pl.apply(b["x"]) - b["y"]) ** 2).mean()
    step_jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(
        plan, {"x": x, "y": y})
    step = Contract(
        name=f"plan_train_step[k={k}]",
        rules=(NoFFT(), NoDenseDotGeneral(), LaunchBudget(exact=3),
               NoWeightConcat()),
    )
    return [(fwd, fwd_jaxpr), (step, step_jaxpr)]


def audit_plan_surfaces(cfg) -> List[Violation]:
    out: List[Violation] = []
    for contract, jaxpr in plan_surfaces(cfg):
        out.extend(run_contract(contract, jaxpr))
    return out


# ---------------------------------------------------------------------------
# Serve surfaces (one live engine, every bucketed executable)
# ---------------------------------------------------------------------------

#: impls whose whole dataflow is kernel-/matmul-backed — their serve traces
#: must contain no fft primitive at all. The ``paper``/``freq`` impls stream
#: activations through rfft by design; for them only the weight side
#: (NoWeightFFT) is contractual.
FFT_FREE_IMPLS = ("pallas", "dft")


def _serve_trace_args(engine, Bb: int, Sb: Optional[int]):
    """Shape-faithful trace arguments for one bucket, mirroring
    ``ServeEngine.prewarm``'s synthesis (all-pad prefill rows / decode
    probes) — shapes are what matter to ``jax.make_jaxpr``."""
    if Sb is None:                           # decode bucket
        args = (engine.params, jnp.zeros((Bb, 1), jnp.int32), engine.cache,
                -jnp.ones((Bb,), jnp.int32), jnp.arange(Bb, dtype=jnp.int32))
        return args, {}
    toks = jnp.zeros((Bb, Sb), jnp.int32)
    pos = (jnp.broadcast_to(jnp.arange(Sb, dtype=jnp.int32), (Bb, Sb)) - Sb)
    slots = jnp.arange(Bb, dtype=jnp.int32)
    kw: Dict[str, Any] = {}
    if engine.prefix_cache:
        kw["donor_idx"] = slots
        kw["match_len"] = jnp.zeros((Bb,), jnp.int32)
    ex = engine.runner.prewarm_extra(Bb)
    if ex is not None:
        kw["extra"] = ex
    return (engine.params, toks, pos, engine.cache, slots), kw


def serve_trace_jaxprs(engine) -> List[Tuple[str, Any]]:
    """``(surface_name, jaxpr)`` for every prefill/decode bucket executable
    of a live engine — the exact functions ``prewarm`` compiles, traced
    unjitted so the structure is inspectable.

    Keyword operands (prefix-cache donors, encoder ``extra`` tokens) are
    threaded as *traced arguments*, not closure captures: a closed-over
    array becomes a trace constant, and the purity analysis would then
    read data derived from it (e.g. a whole encoder pass) as weight-side.
    """
    out = []
    for Sb in engine.prompt_buckets:
        for Bb in engine.prefill_batch_buckets[Sb]:
            args, kw = _serve_trace_args(engine, Bb, Sb)
            kw_leaves, kw_tree = jax.tree.flatten(kw)
            jp = jax.make_jaxpr(
                lambda a, k: engine._prefill_fn(
                    *a, **jax.tree.unflatten(kw_tree, k))
            )(args, kw_leaves)
            out.append((f"serve_prefill[B{Bb},S{Sb}]", jp))
    for Bb in engine.decode_buckets:
        args, _ = _serve_trace_args(engine, Bb, None)
        jp = jax.make_jaxpr(engine._decode_fn)(*args)
        out.append((f"serve_decode[B{Bb}]", jp))
    return out


def _serve_rules(engine) -> Tuple[Any, ...]:
    specs = engine.runner.specs()
    n_params = len(jax.tree.leaves(engine.params))
    rules: List[Any] = [
        NoWeightFFT(n_param_invars=n_params),
        DenseFallbackDot(dense_equivalent_shapes(specs),
                         n_param_invars=n_params),
        NoWeightConcat(fused_table_shapes(engine.params),
                       n_param_invars=n_params),
    ]
    if engine.cfg.swm.impl in FFT_FREE_IMPLS:
        rules.insert(0, NoFFT())
    return tuple(rules)


def _decode_rules(engine) -> Tuple[Any, ...]:
    """The decode surfaces' extra rules: the model step's named scopes,
    for models whose every contraction one of them describes (recurrent
    mixers have no scope of their own)."""
    from repro.serve.runner import recurrent_mixer_names

    if recurrent_mixer_names(engine.cfg):
        return ()
    return (ScopedContractions(),)


def audit_engine(engine, traces=None) -> List[Violation]:
    """All single-engine serve contracts: every bucketed executable's trace
    rules, the frozen-table dtype contract for the engine's quantize mode,
    and lowered-module donation aliasing when ``donate=True``.

    ``traces`` (from :func:`serve_trace_jaxprs`) can be passed in to avoid
    re-tracing when the caller also needs the jaxprs (launch parity)."""
    out: List[Violation] = []
    if not engine.cfg.swm.enabled:
        return out                          # dense config: nothing to promise
    rules = _serve_rules(engine)
    decode_rules = rules + _decode_rules(engine)
    traces = serve_trace_jaxprs(engine) if traces is None else traces
    for name, jp in traces:
        surface_rules = (decode_rules if name.startswith("serve_decode")
                         else rules)
        out.extend(run_contract(Contract(name=name, rules=surface_rules),
                                jp))

    for v in QuantizedTableDtypes(engine.quantize).check_params(
            engine.params):
        out.append(dataclasses.replace(v, surface="serve_params"))

    if engine.donate:
        donated = DonatedInputsAliased()
        for argnums, Sb in (((3,), int(engine.prompt_buckets[0])),
                            ((2,), None)):
            Bb = int(engine.batch_buckets[0] if Sb is not None
                     else engine.decode_buckets[0])
            args, kw = _serve_trace_args(engine, Bb, Sb)
            fn = engine._prefill_fn if Sb is not None else engine._decode_fn
            text = jax.jit(
                lambda *a: fn(*a, **kw), donate_argnums=argnums,
            ).lower(*args).as_text()
            kind = "prefill" if Sb is not None else "decode"
            out.extend(donated.check_lowered(
                text, surface=f"serve_donation[{kind}]"))
    return out


def launch_counts(engine, traces=None) -> Dict[str, int]:
    """Pallas launches per bucketed executable (for cross-engine parity)."""
    from repro.analysis.walker import iter_eqns

    traces = serve_trace_jaxprs(engine) if traces is None else traces
    return {
        name: sum(1 for e in iter_eqns(jp)
                  if e.primitive.name == "pallas_call")
        for name, jp in traces
    }


# ---------------------------------------------------------------------------
# Whole-config audit (the CLI's unit of work)
# ---------------------------------------------------------------------------


def _smoke_engine(model, cfg, params, quantize: str):
    from repro.serve.engine import ServeEngine

    return ServeEngine(model, cfg, params, batch=2, cache_len=32,
                       prompt_buckets=(8,), decode_buckets=(2,),
                       quantize=quantize)


def audit_config(arch: str, quantize_legs: Sequence[str] = ("off", "int8"),
                 ) -> Dict[str, Any]:
    """Audit every surface of one registry config (SMOKE shapes — the
    contracts are structural, so tiny geometry proves the same jaxprs).

    Returns ``{"arch", "impl", "surfaces", "violations": [...]}``; an empty
    ``violations`` list is the pass condition.
    """
    from repro.configs.registry import get_smoke
    from repro.launch.specs import build_model
    from repro.nn.module import init_params

    cfg = get_smoke(arch)
    violations: List[Violation] = []
    surfaces: List[str] = []

    if cfg.swm.enabled:
        for contract, jaxpr in plan_surfaces(cfg):
            surfaces.append(contract.name)
            violations.extend(run_contract(contract, jaxpr))

    model = build_model(cfg)
    params = init_params(model.specs(), 0)
    parity: Dict[str, Dict[str, int]] = {}
    for quantize in quantize_legs:
        if quantize != "off" and not cfg.swm.enabled:
            continue
        eng = _smoke_engine(model, cfg, params, quantize)
        traces = serve_trace_jaxprs(eng)
        vs = audit_engine(eng, traces=traces)
        tag = f"q={quantize}"
        surfaces.extend(f"{n}[{tag}]" for n, _ in traces)
        violations.extend(
            dataclasses.replace(v, surface=f"{v.surface}[{tag}]")
            for v in vs)
        parity[quantize] = launch_counts(eng, traces=traces)

    if "off" in parity and "int8" in parity:
        surfaces.append("serve_launch_parity")
        for name, n_off in parity["off"].items():
            n_q = parity["int8"].get(name)
            if n_q != n_off:
                violations.append(Violation(
                    rule="LaunchParity",
                    surface=f"serve_launch_parity[{name}]",
                    message=f"int8 engine launches {n_q} Pallas kernels "
                            f"where fp32 launches {n_off} — in-kernel "
                            f"dequant must add no launch",
                ))

    return {
        "arch": arch,
        "impl": cfg.swm.impl if cfg.swm.enabled else "dense",
        "surfaces": surfaces,
        "violations": [v.to_json() for v in violations],
    }
