"""Static analysis: structural contracts over traces, and a repo AST lint.

The paper's complexity claims (arXiv:1804.11239 — O(n log n) block-circulant
inference/training, frozen BRAM-resident FFT(w) tables) are only real if the
*compiled programs* have the promised structure. Numerics can be right while
the structure silently regresses: a dense ``dot_general`` fallback, a
re-traced weight ``rfft``, an extra kernel launch, a per-trace weight concat
— all bit-identical, all destroying the asymptotics the repo exists to
demonstrate. This package turns those one-off assertions into a subsystem:

* :mod:`repro.analysis.walker` — the recursive jaxpr traversal (descends
  ``pjit``/``scan``/``while``/``cond``/``custom_vjp`` sub-jaxprs; stops at
  ``pallas_call`` bodies) with ``file:line`` provenance from
  ``eqn.source_info``. ``kernels.block_circulant.ops``'s public probes
  (``count_pallas_launches``/``outer_dot_shapes``) are wrappers over it.
* :mod:`repro.analysis.rules` — named declarative rules (``NoFFT``,
  ``NoWeightFFT``, ``NoDenseDotGeneral``, ``DenseFallbackDot``,
  ``LaunchBudget``, ``NoWeightConcat``, ``ScopedContractions``,
  ``QuantizedTableDtypes``, ``DonatedInputsAliased``) that return
  :class:`Violation`\\ s, never bare booleans.
* :mod:`repro.analysis.contracts` — rules grouped into per-surface
  contracts (frozen-plan forward, train step, every serve prefill/decode
  bucket, int8 serve + launch parity). ``ServeEngine.audit()`` and
  ``train.loop.make_grad_step(audit_args=...)`` hook these into runtime
  gates; ``audit_config`` audits one registry config end to end.
* :mod:`repro.analysis.lint` — AST lint for repo-specific hazards: fft
  outside the blessed modules, wall-clock/unseeded-rng nondeterminism and
  blocking host sync inside ``serve/``, unmarked broad ``except``.

CLI: ``python -m repro.analysis --all-configs --json report.json`` audits
every registry config × surface plus the lint and exits non-zero on any
violation — the CI ``static-analysis`` job's entry point.
"""

from repro.analysis.contracts import (Contract, StructuralContractError,
                                      audit_config, audit_engine,
                                      run_contract)
from repro.analysis.lint import lint_file, lint_paths
from repro.analysis.rules import (DenseFallbackDot, DonatedInputsAliased,
                                  LaunchBudget, NoDenseDotGeneral, NoFFT,
                                  NoWeightConcat, NoWeightFFT,
                                  QuantizedTableDtypes, ScopedContractions,
                                  Violation)
from repro.analysis.walker import (collect_pure_vars, iter_eqns,
                                   iter_scoped_eqns, source_location)

__all__ = [
    "Contract",
    "StructuralContractError",
    "Violation",
    "NoFFT",
    "NoWeightFFT",
    "NoDenseDotGeneral",
    "DenseFallbackDot",
    "LaunchBudget",
    "NoWeightConcat",
    "ScopedContractions",
    "QuantizedTableDtypes",
    "DonatedInputsAliased",
    "audit_config",
    "audit_engine",
    "run_contract",
    "collect_pure_vars",
    "iter_eqns",
    "iter_scoped_eqns",
    "source_location",
    "lint_file",
    "lint_paths",
]
