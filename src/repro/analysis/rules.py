"""Declarative structural rules over traced programs.

Each rule is a small named object with a ``check`` method returning
:class:`Violation`\\ s — never booleans — so every failure carries the rule
name, the offending primitive, and ``file:line`` provenance from
``eqn.source_info``. Rules are grouped into per-surface contracts by
``analysis.contracts``; see that module for which rule gates which surface.

Jaxpr rules (``check(jaxpr)``):

* :class:`NoFFT` — no ``fft`` primitive anywhere in the trace. The frozen
  frequency-domain contract for surfaces whose whole dataflow is
  kernel-/DFT-backed (``impl='pallas'``/``'dft'``, ``BCPlan`` paths).
* :class:`NoWeightFFT` — no ``fft`` consuming *parameter-derived* data,
  decided by a purity taint analysis (``walker.collect_pure_vars``), not by
  shape matching — activation blocks ``(B*S, q, k)`` collide with other
  layers' table shapes. The freeze contract for ``impl='paper'``/``'freq'``
  surfaces, whose activation-side transforms are the paper's dataflow and
  legitimate.
* :class:`NoDenseDotGeneral` — zero ``dot_general`` outside ``pallas_call``
  bodies. Only pure-circulant surfaces can promise this.
* :class:`DenseFallbackDot` — no ``dot_general`` whose parameter-derived
  rank-2 operand has a circulant layer's dense-equivalent ``(in, out)``
  shape: the signature of a silent dense fallback inside a full model that
  also contains legitimate attention/MoE contractions.
* :class:`LaunchBudget` — exact/max ``pallas_call`` count.
* :class:`NoWeightConcat` — no ``concatenate`` producing a stacked frozen
  table shape (fused QKV/LSTM-gate groups must be pre-concatenated by
  ``freeze_params``, never concatenated per-trace).
* :class:`ScopedContractions` — every ``dot_general`` and ``pallas_call``
  sits under one of the model step's ``jax.named_scope`` names
  (:data:`DECODE_SCOPES`), which a profile groups device time by.

Value rules (checked against non-jaxpr artifacts):

* :class:`QuantizedTableDtypes` (``check_params``) — frozen tables are int8
  with f32 per-block scales (``quantize='int8'``) or plain f32 (``'off'``).
* :class:`DonatedInputsAliased` (``check_lowered``) — the lowered module
  text records input-output aliasing for donated buffers.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.walker import (collect_pure_vars, iter_eqns,
                                   iter_scoped_eqns, source_location)

__all__ = [
    "Violation",
    "NoFFT",
    "NoWeightFFT",
    "NoDenseDotGeneral",
    "DenseFallbackDot",
    "LaunchBudget",
    "NoWeightConcat",
    "ScopedContractions",
    "DECODE_SCOPES",
    "QuantizedTableDtypes",
    "DonatedInputsAliased",
]


@dataclasses.dataclass(frozen=True)
class Violation:
    """One broken contract: which rule, on which surface, where in the code."""

    rule: str
    message: str
    surface: str = ""
    primitive: str = ""
    where: Optional[str] = None        # "file.py:line" (or None)

    def __str__(self) -> str:
        loc = f" at {self.where}" if self.where else ""
        prim = f" [{self.primitive}]" if self.primitive else ""
        surf = f"{self.surface}: " if self.surface else ""
        return f"{surf}{self.rule}: {self.message}{prim}{loc}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _flag(rule: str, message: str, eqn=None) -> Violation:
    return Violation(
        rule=rule,
        message=message,
        primitive=eqn.primitive.name if eqn is not None else "",
        where=source_location(eqn) if eqn is not None else None,
    )


class NoFFT:
    """No ``fft`` primitive anywhere (weights *and* activations frozen out)."""

    name = "NoFFT"

    def check(self, jaxpr) -> List[Violation]:
        out = []
        for eqn in iter_eqns(jaxpr):
            if eqn.primitive.name == "fft":
                kind = eqn.params.get("fft_type")
                kind = getattr(kind, "name", kind)
                shape = tuple(eqn.invars[0].aval.shape)
                out.append(_flag(
                    self.name,
                    f"fft ({kind}) over operand shape {shape} in a trace "
                    f"that promises frozen frequency tables and no "
                    f"transform work",
                    eqn,
                ))
        return out


class NoWeightFFT:
    """No ``fft`` consuming parameter-derived (weight) data.

    ``n_param_invars`` is the number of leading flattened invars that are
    parameter leaves (``len(jax.tree.leaves(params))`` when the traced
    callable takes ``params`` first). An fft whose operand derives *only*
    from those invars and trace constants is a weight-side transform — the
    freeze contract broken. Activation transforms are tainted by
    tokens/cache and pass, whatever their shapes (shape matching is not
    sound: ``(B*S, q, k)`` activation blocks collide with other layers'
    ``(p', q', k)`` tables).
    """

    name = "NoWeightFFT"

    def __init__(self, n_param_invars: int):
        self.n_param_invars = int(n_param_invars)

    def check(self, jaxpr) -> List[Violation]:
        pure = collect_pure_vars(jaxpr, [True] * self.n_param_invars)
        out = []
        for eqn in iter_eqns(jaxpr):
            if eqn.primitive.name != "fft":
                continue
            op = eqn.invars[0]
            if hasattr(op, "val") or op not in pure:
                continue                        # token-/cache-tainted: ok
            src = tuple(op.aval.shape)
            dst = tuple(eqn.outvars[0].aval.shape)
            out.append(_flag(
                self.name,
                f"weight-side fft over parameter-derived data "
                f"{src} -> {dst}; frozen plans must carry rfft(w) as "
                f"data (freeze_params), never re-transform per trace",
                eqn,
            ))
        return out


class NoDenseDotGeneral:
    """Zero ``dot_general`` outside ``pallas_call`` bodies (strict)."""

    name = "NoDenseDotGeneral"

    def check(self, jaxpr) -> List[Violation]:
        out = []
        for eqn in iter_eqns(jaxpr):
            if eqn.primitive.name == "dot_general":
                shapes = [tuple(v.aval.shape) for v in eqn.invars]
                out.append(_flag(
                    self.name,
                    f"dense dot_general over {shapes} outside any "
                    f"pallas_call — the circulant path must not fall back "
                    f"to XLA contractions",
                    eqn,
                ))
        return out


class DenseFallbackDot:
    """No ``dot_general`` whose *parameter-derived* rank-2 operand matches a
    circulant layer's dense-equivalent ``(in, out) = (q*k, p*k)`` kernel
    shape. Without ``n_param_invars`` any matching rank-2 operand is
    flagged; with it, token-tainted operands (activations that einsum
    lowering collapsed to ``(B*S, d)`` matrices) pass."""

    name = "DenseFallbackDot"

    def __init__(self, dense_shapes: Iterable[Tuple[int, int]],
                 n_param_invars: Optional[int] = None):
        shapes = {tuple(int(d) for d in s) for s in dense_shapes}
        self.dense_shapes = shapes | {(o, i) for (i, o) in shapes}
        self.n_param_invars = n_param_invars

    def check(self, jaxpr) -> List[Violation]:
        pure = None
        if self.n_param_invars is not None:
            pure = collect_pure_vars(jaxpr, [True] * self.n_param_invars)
        out = []
        for eqn in iter_eqns(jaxpr):
            if eqn.primitive.name != "dot_general":
                continue
            for v in eqn.invars:
                shape = tuple(v.aval.shape)
                if pure is not None and not (hasattr(v, "val") or v in pure):
                    continue
                if len(shape) == 2 and shape in self.dense_shapes:
                    out.append(_flag(
                        self.name,
                        f"dot_general against a {shape} operand — the "
                        f"dense-equivalent kernel of a circulant layer "
                        f"(silent O(n^2) fallback)",
                        eqn,
                    ))
                    break
        return out


class LaunchBudget:
    """Exact (or bounded) number of ``pallas_call`` launches in the trace."""

    name = "LaunchBudget"

    def __init__(self, exact: Optional[int] = None,
                 max_launches: Optional[int] = None):
        if (exact is None) == (max_launches is None):
            raise ValueError("LaunchBudget takes exactly one of "
                             "exact= / max_launches=")
        self.exact, self.max_launches = exact, max_launches

    def check(self, jaxpr) -> List[Violation]:
        launches = [e for e in iter_eqns(jaxpr)
                    if e.primitive.name == "pallas_call"]
        n = len(launches)
        budget = self.exact if self.exact is not None else self.max_launches
        over = (n != self.exact if self.exact is not None
                else n > self.max_launches)
        if not over:
            return []
        kind = "exactly" if self.exact is not None else "at most"
        # point at the first launch beyond the budget when there is one —
        # that is the eqn a regression added
        culprit = launches[budget] if n > budget else (
            launches[-1] if launches else None)
        return [_flag(
            self.name,
            f"{n} pallas_call launches, contract requires {kind} {budget}",
            culprit,
        )]


class NoWeightConcat:
    """No in-trace ``concatenate`` assembling weight tables.

    Strict mode (no arguments): zero concatenate eqns at all — for
    pure-kernel surfaces. Serve mode: pass the fused-group ``(sum_p, q, K)``
    ``table_shapes`` (from the frozen params) and ``n_param_invars``; a
    concat is flagged only when it produces a stacked-table shape *and*
    every operand is parameter-derived — legitimate activation concats
    (e.g. the LSTM ``[x_t ; y_prev]``) are token-tainted and pass.
    """

    name = "NoWeightConcat"

    def __init__(self,
                 table_shapes: Optional[Iterable[Tuple[int, ...]]] = None,
                 n_param_invars: Optional[int] = None):
        self.table_shapes = (
            None if table_shapes is None
            else {tuple(int(d) for d in s) for s in table_shapes}
        )
        self.n_param_invars = n_param_invars

    def check(self, jaxpr) -> List[Violation]:
        pure = None
        if self.n_param_invars is not None:
            pure = collect_pure_vars(jaxpr, [True] * self.n_param_invars)
        out = []
        for eqn in iter_eqns(jaxpr):
            if eqn.primitive.name != "concatenate":
                continue
            shape = tuple(eqn.outvars[0].aval.shape)
            if self.table_shapes is not None and shape not in self.table_shapes:
                continue
            if pure is not None and not all(
                    hasattr(v, "val") or v in pure for v in eqn.invars):
                continue
            out.append(_flag(
                self.name,
                f"concatenate producing {shape} — fused weight groups must "
                f"be pre-concatenated once by freeze_params, not stacked "
                f"inside every cached executable",
                eqn,
            ))
        return out


#: The model step's stable ``jax.named_scope`` names: ``circulant`` (the
#: ``core.circulant`` projection entry points), ``attention``
#: (``Attention.__call__``, ``MLAttention.__call__``), ``kv_move`` (a
#: runner's ``gather_state`` / ``place_state``, and ``write_cache``: in
#: decode, only the new entries written into the slot pool), ``head``
#: (the logits head) and ``moe`` (``MoE.__call__``: router, top-k,
#: dispatch, combine). A device op's profiler name carries them as path
#: segments; the innermost one names its layer.
DECODE_SCOPES = ("circulant", "attention", "kv_move", "head", "moe")


class ScopedContractions:
    """Every ``dot_general`` and ``pallas_call`` sits under one of
    :data:`DECODE_SCOPES`, at any depth. A refactor that drops a scope
    would otherwise move its device time into a profile's unattributed
    rest without a sound."""

    name = "ScopedContractions"
    PRIMITIVES = ("dot_general", "pallas_call")

    def check(self, jaxpr) -> List[Violation]:
        out = []
        for eqn, scopes in iter_scoped_eqns(jaxpr):
            if (eqn.primitive.name in self.PRIMITIVES
                    and not set(DECODE_SCOPES).intersection(scopes)):
                out.append(_flag(
                    self.name,
                    f"{eqn.primitive.name} under scopes {list(scopes)}, "
                    f"none of {list(DECODE_SCOPES)}",
                    eqn,
                ))
        return out


class QuantizedTableDtypes:
    """Frozen-table dtype contract over a params tree (value rule).

    ``mode='int8'``: every frozen group (a dict carrying ``wr``/``wi``) must
    store int8 tables with a float32 ``w_scale``. ``mode='off'``: tables are
    float32 and carry no scale.
    """

    name = "QuantizedTableDtypes"

    def __init__(self, mode: str = "int8"):
        if mode not in ("off", "int8"):
            raise ValueError(f"unknown quantize mode {mode!r}")
        self.mode = mode

    def check_params(self, params) -> List[Violation]:
        out: List[Violation] = []

        def visit(node, path):
            if isinstance(node, dict):
                if "wr" in node and "wi" in node:
                    out.extend(self._check_group(node, path))
                for k, v in node.items():
                    visit(v, path + (str(k),))
            elif isinstance(node, (tuple, list)):
                for i, v in enumerate(node):
                    visit(v, path + (str(i),))

        visit(params, ())
        return out

    def _check_group(self, group: dict, path) -> List[Violation]:
        import jax.numpy as jnp

        loc = "/".join(path) or "<root>"
        wr, wi = group["wr"], group["wi"]
        scale = group.get("w_scale")
        bad = []
        if self.mode == "int8":
            if scale is None:
                bad.append(f"frozen table {loc!r} has no w_scale under "
                           f"quantize='int8'")
            else:
                if scale.dtype != jnp.float32:
                    bad.append(f"{loc}/w_scale is {scale.dtype}, "
                               f"contract requires float32")
                for name, t in (("wr", wr), ("wi", wi)):
                    if t.dtype != jnp.int8:
                        bad.append(f"{loc}/{name} is {t.dtype}, "
                                   f"contract requires int8")
        else:
            if scale is not None:
                bad.append(f"frozen table {loc!r} carries w_scale under "
                           f"quantize='off'")
            for name, t in (("wr", wr), ("wi", wi)):
                if not jnp.issubdtype(t.dtype, jnp.floating):
                    bad.append(f"{loc}/{name} is {t.dtype}, contract "
                               f"requires a float dtype")
        return [Violation(rule=self.name, message=m) for m in bad]


class DonatedInputsAliased:
    """Donated buffers actually alias outputs in the lowered module.

    Donation is invisible in jaxprs; the evidence lives in the StableHLO
    text as ``tf.aliasing_output`` (jax<=0.4) / ``jax.buffer_donor``
    argument attributes. ``check_lowered`` takes ``lowered.as_text()``.
    """

    name = "DonatedInputsAliased"
    MARKERS = ("tf.aliasing_output", "jax.buffer_donor")

    def check_lowered(self, text: str,
                      surface: str = "") -> List[Violation]:
        if any(m in text for m in self.MARKERS):
            return []
        return [Violation(
            rule=self.name,
            surface=surface,
            message="no input-output aliasing attribute in the lowered "
                    "module — donate_argnums did not take, so decode "
                    "round-trips the cache through fresh HBM",
        )]
