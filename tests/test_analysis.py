"""repro.analysis: kernel-contract auditor (pass/fail fixtures with
injected violations), epoch-protocol checker (injected stale-commit
race + clean traces from both engines), and golden lint violations per
rule."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    ContractViolation,
    EpochReplay,
    KernelContract,
    audit,
    audit_hlo,
    check_scheduler_source,
    check_timeline,
    count_pallas_calls,
    repo_contracts,
)
from repro.analysis.lint import (
    DEPRECATED_CACHE_FIELDS,
    lint_source,
)
from repro.analysis.protocol import extract_scheduler_events
from repro.cache import CacheConfig
from repro.configs import dlrm as dlrm_cfg
from repro.kernels import ops as kops
from repro.models import dlrm as dlrm_mod
from repro.serving.engine import CTRRequest, make_dlrm_engine


# ---------------------------------------------------------------------------
# Contract auditor: pass fixtures
# ---------------------------------------------------------------------------

def _tbe_args(T=4, R=64, D=16, B=8, L=4):
    return (jax.ShapeDtypeStruct((T, R, D), jnp.float32),
            jax.ShapeDtypeStruct((T, B, L), jnp.int32),
            jax.ShapeDtypeStruct((T, B, L), jnp.float32))


def _tbe_fused(t, i, w):
    return kops.embedding_bag_batched(t, i, None, w, mode="interpret",
                                      fused=True)


def test_every_attached_contract_passes_its_fixture():
    """The repo-wide gate: every KERNEL_CONTRACTS entry audits clean
    over its canonical fixture (same code path the CLI runs)."""
    from repro.analysis.fixtures import run_all

    reports = run_all()
    assert len(reports) == len(repo_contracts())
    for report in reports:
        assert report.ok, (report.contract.name, report.violations)


def test_audit_counts_nested_launches():
    """The walker must find pallas_call inside custom_vjp/pjit
    sub-jaxprs — the ad-hoc str().count() it replaced did (textually);
    regressing to a top-level-only walk would pass everything."""
    n = count_pallas_calls(_tbe_fused, *_tbe_args())
    assert n == 1


# ---------------------------------------------------------------------------
# Contract auditor: injected violations (fail fixtures)
# ---------------------------------------------------------------------------

def test_injected_second_launch_is_caught():
    """Acceptance criterion: an injected second pallas_call launch must
    fail the single-launch contract."""
    contract = kops.KERNEL_CONTRACTS["tbe_fused"]

    def two_launches(t, i, w):
        return _tbe_fused(t, i, w) + _tbe_fused(t, i, w)

    report = audit(two_launches, _tbe_args(), contract)
    assert not report.ok
    assert report.summary.pallas_calls == 2
    assert any("launches: got 2" in v for v in report.violations)
    with pytest.raises(ContractViolation, match="got 2"):
        report.raise_if_failed()
    # and the clean program still passes the same contract
    audit(_tbe_fused, _tbe_args(), contract).raise_if_failed()


def test_forbidden_collective_is_caught():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()), ("x",))
    fn = shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                   in_specs=P("x"), out_specs=P())
    args = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    strict = KernelContract(name="no-collectives", min_pallas_calls=0,
                            max_pallas_calls=0)
    report = audit(fn, args, strict)
    assert any("psum" in v for v in report.violations)
    # whitelisting the collective makes the same program pass (a checked
    # shard_map traces lax.psum as the "psum_invariant" primitive)
    allowed = dataclasses.replace(
        strict, allowed_collectives=("psum", "psum_invariant"))
    audit(fn, args, allowed).raise_if_failed()


def test_collective_set_covers_installed_jax():
    """Every primitive ``jax._src.lax.parallel`` defines is either a
    collective the auditor counts or listed as moving no data."""
    from jax._src import core
    from jax._src.lax import parallel

    from repro.analysis.contracts import (COLLECTIVE_PRIMITIVES,
                                          NON_COLLECTIVE_PARALLEL)

    defined = {v.name for v in vars(parallel).values()
               if isinstance(v, core.Primitive)}
    assert defined - NON_COLLECTIVE_PARALLEL <= COLLECTIVE_PRIMITIVES
    assert NON_COLLECTIVE_PARALLEL <= defined


def test_dropped_donation_is_caught():
    def scatter(pool, addr, rows):
        return pool.at[addr].set(rows)

    args = (jax.ShapeDtypeStruct((64, 8), jnp.float32),
            jax.ShapeDtypeStruct((4,), jnp.int32),
            jax.ShapeDtypeStruct((4, 8), jnp.float32))
    contract = KernelContract(name="donated-scatter", min_pallas_calls=0,
                              max_pallas_calls=0, donate_argnums=(0,))
    donated = functools.partial(jax.jit, donate_argnums=(0,))(scatter)
    audit(donated, args, contract).raise_if_failed()

    dropped = jax.jit(scatter)          # the regression: donation lost
    report = audit(dropped, args, contract)
    assert any("not donated" in v for v in report.violations)


def test_float_upcast_is_caught():
    def upcasts(x):
        return (x.astype(jnp.float32) * 2).astype(jnp.bfloat16)

    args = (jax.ShapeDtypeStruct((8,), jnp.bfloat16),)
    ceiling16 = KernelContract(name="bf16-only", min_pallas_calls=0,
                               max_pallas_calls=0, max_float_bits=16)
    report = audit(upcasts, args, ceiling16)
    assert any("float32" in v and "ceiling" in v
               for v in report.violations)
    audit(upcasts, args,
          dataclasses.replace(ceiling16,
                              max_float_bits=32)).raise_if_failed()


def test_host_callback_is_caught():
    def with_callback(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((4,),
                                                          jnp.float32), x)

    args = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    contract = KernelContract(name="no-callbacks", min_pallas_calls=0,
                              max_pallas_calls=0)
    report = audit(with_callback, args, contract)
    assert any("pure_callback" in v for v in report.violations)


def test_audit_hlo_flags_compiled_collectives():
    clean = "ROOT %r = f32[8]{0} add(%a, %b)"
    contract = repo_contracts()["serving.engine.tiered_forward"]
    audit_hlo(clean, contract).raise_if_failed()
    dirty = ('%ar = f32[8]{0} all-reduce(%a), replica_groups={{0,1}}, '
             'to_apply=%sum')
    report = audit_hlo(dirty, contract)
    assert any("all-reduce" in v for v in report.violations)


# ---------------------------------------------------------------------------
# Epoch protocol: state machine + static scheduler check
# ---------------------------------------------------------------------------

def _clean_schedule(batches=3):
    events = []
    ring = 0
    for _ in range(batches):
        e = ring + 1
        events += [("prepare", e), ("fetch", e), ("commit", e),
                   ("serve", e), ("swap",)]
        ring += 1
    return events


def test_epoch_replay_clean_schedule_is_silent():
    assert EpochReplay().replay(_clean_schedule()) == []


def test_epoch_replay_flags_injected_stale_commit():
    """Acceptance criterion: a deliberately injected stale-commit race
    (swap slipped in between prepare and commit, so the plan targets an
    already-published epoch) must be flagged."""
    racy = [("prepare", 1), ("fetch", 1), ("commit", 1), ("serve", 1),
            ("swap",),
            ("prepare", 2), ("fetch", 2),
            ("swap",),                      # injected: dropped/double swap
            ("commit", 2)]                  # now stale: ring is already 2
    violations = EpochReplay().replay(racy)
    kinds = {v.kind for v in violations}
    assert "stale-commit" in kinds
    # the injected swap itself published an uncommitted epoch
    assert "swap-uncommitted" in kinds


def test_epoch_replay_flags_double_commit():
    racy = [("prepare", 1), ("fetch", 1), ("commit", 1), ("commit", 1)]
    kinds = {v.kind for v in EpochReplay().replay(racy)}
    assert "double-commit" in kinds


def test_real_pool_refuses_the_same_stale_commit():
    """The replay's stale-commit rule is the REAL commit_next predicate:
    the live DoubleBufferedSlotPool raises on the identical schedule."""
    from repro.core.embedding_bag import EmbeddingBagConfig, init_tables
    from repro.pipeline import DoubleBufferedSlotPool

    cfg = EmbeddingBagConfig(num_tables=2, rows_per_table=64, dim=8,
                             kernel_mode="reference",
                             cache=CacheConfig(rows=16))
    pool = DoubleBufferedSlotPool(init_tables(jax.random.key(0), cfg),
                                  cfg, depth=2)
    idx = np.arange(8, dtype=np.int32).reshape(2, 2, 2)
    lens = np.full((2, 2), 2, np.int32)
    plan = pool.prepare_next(idx, lens)
    rows = pool.fetch_next(plan)
    pool.swap()                                  # injected extra swap
    with pytest.raises(RuntimeError, match="stale prefetch plan"):
        pool.commit_next(plan, rows)


def test_scheduler_source_satisfies_protocol():
    assert check_scheduler_source() == []
    # and the extractor sees the canonical per-batch order
    events = extract_scheduler_events()
    assert [e for e in events
            if e in ("prepare", "fetch", "commit", "serve", "swap")] == \
        ["prepare", "fetch", "commit", "serve", "swap"]


def test_scheduler_source_reordering_is_caught():
    """A tampered scheduler that swaps before committing must fail the
    static call-order check."""
    tampered = """
def run(self, batches):
    for payload in batches:
        plan = self.pool.prepare_next(payload)
        rows = self.pool.fetch_next(plan)
        self.pool.swap()
        self.pool.commit_next(plan, rows)
        self.forward(payload)
"""
    violations = check_scheduler_source(tampered)
    kinds = {v.kind for v in violations}
    assert "stale-commit" in kinds or "swap-uncommitted" in kinds


def test_scheduler_source_missing_stage_is_caught():
    violations = check_scheduler_source(
        "def run(self):\n    self.pool.prepare_next(None)\n")
    assert violations and violations[0].kind == "missing-stage"


# ---------------------------------------------------------------------------
# Epoch protocol: happens-before timeline sanitizer
# ---------------------------------------------------------------------------

def _span(stage, batch, start, end):
    return {"stage": stage, "batch": batch, "start": start, "end": end}


def test_timeline_clean_synthetic_pipeline_accepted():
    # depth 2: batch k scatters slot (k+1)%2 strictly before batch k's
    # forward; batch k+1's scatter overlaps batch k's forward but they
    # target DIFFERENT slots — the pipeline's whole point
    spans = [
        _span("scatter", 0, 0.0, 1.0), _span("forward", 0, 1.5, 3.0),
        _span("scatter", 1, 1.6, 2.5), _span("forward", 1, 3.1, 4.5),
        _span("scatter", 2, 3.2, 4.0), _span("forward", 2, 4.6, 5.0),
    ]
    assert check_timeline(spans, depth=2) == []


def test_timeline_flags_synthetic_buffer_race():
    """Batch 2's scatter targets slot (2+1)%2 = 1 — the SAME slot batch
    0's forward reads — while that forward is still open: the race the
    sanitizer exists to catch."""
    spans = [
        _span("scatter", 0, 0.0, 1.0),
        _span("forward", 0, 1.5, 4.0),           # still reading slot 1...
        _span("scatter", 2, 2.0, 3.0),           # ...while this writes it
    ]
    violations = check_timeline(spans, depth=2)
    assert any(v.kind == "buffer-race" for v in violations)


def test_timeline_flags_scatter_after_own_dispatch():
    spans = [_span("scatter", 0, 1.0, 3.0), _span("forward", 0, 2.0, 4.0)]
    violations = check_timeline(spans, depth=2)
    assert any(v.kind == "scatter-after-dispatch" for v in violations)


def test_timeline_flags_scatter_entirely_after_own_forward():
    """Ordering, not overlap: a scatter that runs strictly AFTER its own
    forward already finished never overlaps it, yet the forward read an
    uncommitted buffer — must still be flagged."""
    spans = [_span("forward", 0, 1.0, 2.0), _span("scatter", 0, 5.0, 6.0)]
    violations = check_timeline(spans, depth=2)
    assert any(v.kind == "scatter-after-dispatch" for v in violations)


def _zipf_requests(cfg, n, rng):
    T, L, F = (cfg.num_sparse_features, cfg.pooling,
               cfg.num_dense_features)
    return [CTRRequest(
        rid=rid, dense=rng.standard_normal(F).astype(np.float32),
        indices=np.minimum(rng.zipf(1.2, size=(T, L)) - 1,
                           cfg.rows_per_table - 1).astype(np.int32),
        lengths=rng.integers(1, L + 1, T).astype(np.int32))
        for rid in range(n)]


def test_timeline_accepts_real_engine_traces():
    """Recorded timelines from BOTH live engines must replay clean:
    the pipelined engine's own StageSpans (depth 2), and the serialized
    engine rendered as a degenerate depth-1 schedule."""
    base = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference",
                               cache=CacheConfig(rows=24))
    piped_cfg = dataclasses.replace(
        base, cache=dataclasses.replace(base.cache, pipeline_depth=2))
    params = dlrm_mod.init_params(jax.random.key(0), base)
    rng = np.random.default_rng(1)

    piped = make_dlrm_engine(params, piped_cfg, batch_size=4)
    for r in _zipf_requests(piped_cfg, 16, rng):
        piped.submit(r)
    piped.run_to_completion()
    spans = piped.trace.spans
    assert spans, "pipelined engine must record stage spans"
    assert check_timeline(spans, depth=2) == []

    serial = make_dlrm_engine(params, base, batch_size=4)
    t = [0.0]

    def stamp(w):
        start = t[0]
        t[0] += w
        return start, t[0]

    serial_spans = []
    for r in _zipf_requests(base, 16, rng):
        serial.submit(r)
    out = serial.run_to_completion()
    assert len(out) == 16
    stats = serial.cache_stats()
    # serialized flushes are strictly ordered: prefetch+scatter then
    # forward, batch by batch — render that schedule at depth 1
    for k in range(stats.batches):
        s0, s1 = stamp(1.0)
        serial_spans.append(_span("scatter", k, s0, s1))
        f0, f1 = stamp(1.0)
        serial_spans.append(_span("forward", k, f0, f1))
    assert check_timeline(serial_spans, depth=1) == []


# ---------------------------------------------------------------------------
# Lint: one golden violation per rule
# ---------------------------------------------------------------------------

def _rules(src, path="pkg/mod.py"):
    return [v.rule for v in lint_source(src, path)]


def test_lint_deprecated_cache_field():
    src = ("import dataclasses\n"
           "from repro.core.embedding_bag import EmbeddingBagConfig\n"
           "cfg = EmbeddingBagConfig(num_tables=2, cache_rows=8)\n"
           "old = dataclasses.replace(cfg, cache_policy='lru')\n")
    assert _rules(src).count("deprecated-cache-field") == 2
    # CacheConfig's REAL fields never flag (cold_tier etc. on replace)
    clean = ("import dataclasses\n"
             "cc = dataclasses.replace(cfg.cache, cold_tier='remote',\n"
             "                         pipeline_depth=2)\n")
    assert _rules(clean) == []


def test_lint_alias_mirror_matches_configs():
    """DEPRECATED_CACHE_FIELDS must stay the exact union of the two
    config classes' alias tuples (lint cannot import them itself)."""
    from repro.configs.dlrm import DLRMConfig
    from repro.core.embedding_bag import EmbeddingBagConfig

    assert DEPRECATED_CACHE_FIELDS == \
        frozenset(EmbeddingBagConfig._CACHE_ALIASES) | \
        frozenset(DLRMConfig._CACHE_ALIASES)


def test_lint_wall_clock():
    assert _rules("import time\nt0 = time.time()\n") == ["wall-clock"]
    assert _rules("import time\nt0 = time.perf_counter()\n") == []


def test_lint_frozen_mutation():
    flagged = ("def resize(cfg, rows):\n"
               "    object.__setattr__(cfg, 'rows', rows)\n")
    assert _rules(flagged) == ["frozen-mutation"]
    exempt = ("class C:\n"
              "    def __post_init__(self):\n"
              "        object.__setattr__(self, 'rows', 4)\n")
    assert _rules(exempt) == []


def test_lint_adhoc_jaxpr_assert():
    src = "assert str(jx).count('pallas_call') == 1\n"
    assert _rules(src) == ["adhoc-jaxpr-assert"]


def test_lint_export_drift():
    stale = ("__all__ = ['real', 'ghost', 'real']\n"
             "def real():\n    pass\n")
    rules = _rules(stale)
    assert rules.count("export-drift") == 2     # stale name + duplicate
    clean = "__all__ = ['real']\ndef real():\n    pass\n"
    assert _rules(clean) == []


def test_lint_export_drift_sees_all_module_scope_bindings():
    """Names bound by for-loops, `with ... as`, walrus, and unpacking at
    module scope are legitimate exports; names bound only inside a
    function or comprehension are not."""
    clean = ("__all__ = ['looped', 'ctx', 'walrus', 'a', 'b']\n"
             "for looped in (1, 2):\n    pass\n"
             "with open('x') as ctx:\n    pass\n"
             "if (walrus := 3):\n    pass\n"
             "a, (b, _) = 1, (2, 3)\n")
    assert _rules(clean) == []
    nested = ("__all__ = ['inner', 'comp']\n"
              "def outer():\n    inner = 1\n"
              "vals = [comp for comp in (1, 2)]\n")
    assert _rules(nested).count("export-drift") == 2


def test_lint_schema_pin_key_drift():
    """Changing a pinned schema's keys WITHOUT bumping the version is a
    violation; bumping the version flips it to a pin-update reminder."""
    drifted = (
        "SLO_EVENT_SCHEMA_VERSION = 1\n"
        "def to_dict(self):\n"
        "    return {\n"
        "        'schema_version': SLO_EVENT_SCHEMA_VERSION,\n"
        "        'kind': 1, 'rule': 1, 'tick': 1, 'engine': 1,\n"
        "        'measured': 1, 'renamed_threshold': 1,\n"
        "        'table': 1, 'expected': 1,\n"
        "    }\n")
    violations = lint_source(drifted, "src/repro/obs/slo.py")
    assert [v.rule for v in violations] == ["schema-pin"]
    assert "bump" in violations[0].message

    bumped = drifted.replace("SLO_EVENT_SCHEMA_VERSION = 1",
                             "SLO_EVENT_SCHEMA_VERSION = 2")
    violations = lint_source(bumped, "src/repro/obs/slo.py")
    assert [v.rule for v in violations] == ["schema-pin"]
    assert "update" in violations[0].message


def test_lint_schema_pin_subscript_keys_counted():
    """Conditionally-assigned keys (d['table'] = ...) are part of the
    pinned key set — the real SLOEvent.to_dict shape."""
    src = (
        "SLO_EVENT_SCHEMA_VERSION = 1\n"
        "def to_dict(self):\n"
        "    d = {\n"
        "        'schema_version': SLO_EVENT_SCHEMA_VERSION,\n"
        "        'kind': 1, 'rule': 1, 'tick': 1, 'engine': 1,\n"
        "        'measured': 1, 'threshold': 1,\n"
        "    }\n"
        "    d['table'] = 1\n"
        "    d['expected'] = 1\n"
        "    return d\n")
    assert lint_source(src, "src/repro/obs/slo.py") == []


def test_lint_suppression_requires_reason():
    # the marker is concatenated so THIS file's raw source never
    # contains a reasonless allow (the suppression scanner reads lines,
    # not the AST, and lint_paths covers tests/)
    allow = "# lint: " + "allow[wall-clock]"
    reasoned = ("import time\n"
                "t = time.time()  " + allow +
                " -- epoch stamp for artifacts\n")
    assert _rules(reasoned) == []
    bare = "import time\nt = time.time()  " + allow + "\n"
    rules = _rules(bare)
    assert "suppression-missing-reason" in rules
    assert "wall-clock" in rules          # the allow did NOT suppress


def test_lint_tree_is_clean():
    """The standing gate: zero unsuppressed violations on the tree."""
    from repro.analysis.lint import lint_paths

    assert lint_paths(["src", "tests", "benchmarks"]) == []
