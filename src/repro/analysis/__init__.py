"""``repro.analysis`` — static contracts for the paper's invariants.

Design note
-----------

The repo's load-bearing claims are STRUCTURAL, not numerical: one fused
gather+pool ``pallas_call`` per forward (PR 1), zero collectives and
zero host callbacks on the cached serving path (PR 2/3), a donated
in-place slot-pool scatter (PR 6), and a race-free epoch protocol under
the double-buffered pipeline (PR 4).  Numerical tests can only show
these held ON THE RUN THEY MEASURED; this package checks the structure
itself, and is wired into CI as a standing gate.

Three layers, by what they inspect:

  ``contracts``  traced/compiled PROGRAMS.  Hot modules attach
      declarative :class:`~repro.analysis.contracts.KernelContract`
      specs (``KERNEL_CONTRACTS`` dicts in ``kernels/ops.py``,
      ``cache/cached_bag.py``, ``core/embedding_bag.py``,
      ``serving/engine.py``, ``cache/tiers.py``);
      :func:`~repro.analysis.contracts.audit` walks the jaxpr
      (recursively, through pjit/shard_map/custom_vjp sub-jaxprs),
      checks launch counts, collective sets, dtype ceilings, callback
      bans, and donation markers in the lowering, and
      :func:`~repro.analysis.contracts.audit_hlo` applies the
      collective rules to compiled post-SPMD HLO.
  ``protocol``   the PIPELINE.  The epoch state machine as replayable
      transitions (:class:`~repro.analysis.protocol.EpochReplay`),
      static call-order validation of the real scheduler source, and a
      happens-before sanitizer over recorded stage timelines
      (:func:`~repro.analysis.protocol.check_timeline`).
  ``lint``       the SOURCE TREE.  AST rules for this repo's real
      failure modes (deprecated flat cache fields, wall-clock misuse,
      frozen-config mutation, serialization-schema drift vs pinned
      key sets, ``__all__`` drift, ad-hoc jaxpr string matching), with
      reason-required per-line suppressions.

Layering rule: this package's import-time dependencies are stdlib-only
(jax is imported lazily inside functions; ``fixtures`` — which imports
the hot modules — is loaded only by the CLI).  Hot modules may
therefore import ``repro.analysis.contracts`` to declare their
contracts without cycles, and the lint/protocol layers stay usable on
a tree whose runtime modules don't even import.

CLI: ``python -m repro.analysis`` (``--lint --contracts --protocol``,
default all three).  Exit 1 on any
violation — the CI ``static-analysis`` job is exactly this command.
"""
from repro.analysis.contracts import (
    AuditReport,
    ContractViolation,
    KernelContract,
    audit,
    audit_hlo,
    count_pallas_calls,
    donated_argnums,
    parse_collectives,
    repo_contracts,
    summarize,
)
from repro.analysis.lint import (
    LintViolation,
    lint_paths,
    lint_source,
)
from repro.analysis.protocol import (
    EpochReplay,
    ProtocolViolation,
    check_scheduler_source,
    check_timeline,
)

__all__ = [
    "AuditReport",
    "ContractViolation",
    "KernelContract",
    "audit",
    "audit_hlo",
    "count_pallas_calls",
    "donated_argnums",
    "parse_collectives",
    "repo_contracts",
    "summarize",
    "LintViolation",
    "lint_paths",
    "lint_source",
    "EpochReplay",
    "ProtocolViolation",
    "check_scheduler_source",
    "check_timeline",
]
