"""Recursive jaxpr walker with source provenance.

The single traversal primitive behind every structural probe in the repo.
``iter_eqns`` yields each equation of a (closed) jaxpr *and* of every
sub-jaxpr reachable through equation params — ``pjit`` bodies, ``scan`` /
``while`` / ``cond`` branches, ``custom_vjp``/``custom_jvp`` calls, and any
future higher-order primitive that stashes a Jaxpr/ClosedJaxpr (or a
tuple/list/dict of them) in its params. The one deliberate boundary is
``pallas_call``: kernel bodies are tiled VMEM programs, not XLA dataflow,
so rules that ask "does the *outer* program contain X" must not see inside
a launch. Pass ``into_pallas=True`` to lift that boundary.

``source_location`` maps an equation back to the user frame that traced it
(``file.py:line``), so rule violations point at code, not at a count
mismatch. ``iter_scoped_eqns`` walks the same way and yields each
equation's ``jax.named_scope`` names, outermost first, with those of the
equations that enclose it: a sub-jaxpr's own name stacks start empty.

This module must stay dependency-free within ``repro`` — it is imported by
``kernels.block_circulant.ops`` (whose public probes are thin wrappers over
``iter_eqns``) and by ``analysis.rules``/``analysis.contracts``.
"""

from __future__ import annotations

from typing import Iterator, Optional

__all__ = [
    "as_jaxpr",
    "collect_pure_vars",
    "iter_eqns",
    "iter_scoped_eqns",
    "iter_sub_jaxprs",
    "source_location",
]


def as_jaxpr(jaxpr):
    """Unwrap a ClosedJaxpr (or anything with ``.jaxpr``) to the bare Jaxpr."""
    return getattr(jaxpr, "jaxpr", jaxpr)


def iter_sub_jaxprs(val) -> Iterator:
    """Yield every (bare) Jaxpr held inside an eqn-params value.

    Handles Jaxpr, ClosedJaxpr, and arbitrarily nested tuples/lists/dicts of
    them (``cond`` stores a tuple of branches; ``scan``/``pjit`` store a
    single ClosedJaxpr; ``custom_vjp`` stores callables wrapping jaxprs —
    those surface through their ``call_jaxpr``/``fun_jaxpr`` params).
    """
    if hasattr(val, "jaxpr"):                   # ClosedJaxpr (also has .eqns)
        yield val.jaxpr
    elif hasattr(val, "eqns"):                  # bare Jaxpr
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from iter_sub_jaxprs(v)
    elif isinstance(val, dict):
        for v in val.values():
            yield from iter_sub_jaxprs(v)


def iter_eqns(jaxpr, *, into_pallas: bool = False) -> Iterator:
    """Depth-first over every eqn in ``jaxpr`` and all nested sub-jaxprs.

    ``pallas_call`` eqns are always yielded themselves; their kernel body is
    only descended into when ``into_pallas=True``.
    """
    for eqn, _ in iter_scoped_eqns(jaxpr, into_pallas=into_pallas):
        yield eqn


def iter_scoped_eqns(jaxpr, *, into_pallas: bool = False) -> Iterator:
    """``iter_eqns`` yielding ``(eqn, scopes)``: the ``jax.named_scope``
    names in force where ``eqn`` was traced, outermost first, including
    those of every equation whose sub-jaxpr holds it (transform entries
    of a name stack, such as ``jvp``, are not scopes)."""
    from jax._src.source_info_util import Scope

    stack = [(as_jaxpr(jaxpr), ())]
    while stack:
        jx, outer = stack.pop()
        for eqn in jx.eqns:
            scopes = outer + tuple(e.name for e in
                                   eqn.source_info.name_stack.stack
                                   if isinstance(e, Scope))
            yield eqn, scopes
            if eqn.primitive.name == "pallas_call" and not into_pallas:
                continue
            for val in eqn.params.values():
                stack.extend((sub, scopes) for sub in iter_sub_jaxprs(val))


def _is_literal(v) -> bool:
    return hasattr(v, "val")                   # Literal carries a value


def collect_pure_vars(jaxpr, pure_invars) -> set:
    """Vars (at any nesting depth) that derive ONLY from the invars marked
    pure plus trace constants — i.e. carry no dependence on the impure
    invars.

    ``pure_invars`` is a bool per top-level invar (e.g. True for the
    flattened params leaves, False for tokens/cache). Constvars and
    literal-/iota-style no-input eqns count as pure: a weight table baked
    into the trace as a constant is still weight data. The serve contracts
    use this to tell a weight-side ``rfft`` (pure operand — the freeze
    contract broken) from the paper's legitimate activation transforms
    (token-tainted operands).

    Sub-jaxpr invars are aligned to the tail of ``eqn.invars`` (the layout
    of scan/pjit/cond operand conventions); unalignable leading invars are
    conservatively impure, so approximation errors only ever *hide* a pure
    var, never invent one.

    Sub-jaxprs are deduplicated by the tracer (two ``rfft`` call sites share
    one jaxpr object, hence one set of inner vars), so a sub-jaxpr's mask is
    the meet (AND) of its masks over *all* call sites, iterated to fixpoint:
    an inner var is pure only if every caller feeds it pure data. Same
    conservative direction — sharing can only demote, never promote.
    """
    root = as_jaxpr(jaxpr)
    mask0 = list(pure_invars) + [False] * (len(root.invars) - len(pure_invars))
    masks = {id(root): mask0[:len(root.invars)]}

    def meet(jx, mask) -> bool:
        old = masks.get(id(jx))
        if old is None:
            masks[id(jx)] = list(mask)
            return True
        new = [a and b for a, b in zip(old, mask)]
        if new != old:
            masks[id(jx)] = new
            return True
        return False

    changed = True
    pure: set = set()
    while changed:
        changed = False
        pure = set()

        def visit(jx):
            nonlocal changed
            pure.update(jx.constvars)
            for v, is_pure in zip(jx.invars, masks[id(jx)]):
                if is_pure:
                    pure.add(v)
            for eqn in jx.eqns:
                if all(_is_literal(v) or v in pure for v in eqn.invars):
                    pure.update(eqn.outvars)
                if eqn.primitive.name == "pallas_call":
                    continue
                for val in eqn.params.values():
                    for sub in iter_sub_jaxprs(val):
                        m = len(sub.invars)
                        tail = eqn.invars[-m:] if m else []
                        sub_mask = [False] * (m - len(tail)) + [
                            _is_literal(v) or v in pure for v in tail]
                        if meet(sub, sub_mask):
                            changed = True
                        visit(sub)

        visit(root)
    return pure


def source_location(eqn) -> Optional[str]:
    """``"path/to/file.py:line"`` of the user frame that traced ``eqn``,
    or None when provenance is unavailable (e.g. synthesized eqns)."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is None:
            # fall back to the innermost frame (library code) rather than
            # dropping provenance entirely
            frames = list(source_info_util.user_frames(eqn.source_info.traceback))
            frame = frames[0] if frames else None
        if frame is None:
            return None
        return f"{frame.file_name}:{frame.start_line}"
    except (ImportError, AttributeError):  # jax-internal API drift
        return None
