"""Project-specific scoping for repro-lint: which trees each checker walks
and which call sites are *declared* configuration entry points.

The determinism contract (docs/SIMULATION.md) binds the simulation path —
engines, experiment plumbing, traces, benches, examples — not the live
serving/model stack, which legitimately reads wall clocks and env vars. The
scopes below encode that boundary once, so checkers don't grow per-file
carve-outs; point sanctions inside scoped code use inline
``# repro-lint: allow[rule]`` pragmas instead (docs/ANALYSIS.md).
"""
from __future__ import annotations

from typing import Dict, Set, Tuple

#: Trees the determinism checker walks: every module whose behavior must be
#: a pure function of (spec, seed). ``src/repro/core/`` includes
#: ``traces.py`` and both fleet engines. ``tools/`` self-hosts: the
#: analyzers and CI gates obey the same rules they enforce.
DETERMINISM_SCOPE: Tuple[str, ...] = (
    "src/repro/core/",
    "src/repro/experiments/",
    "benchmarks/",
    "examples/",
    "tools/",
)

#: Trees the float-determinism checker walks: code shared by the scalar and
#: vectorized engines, where an order-sensitive reduction (unstable sort,
#: accumulation over a set) silently breaks the bit-identity contract
#: (docs/SIMULATION.md, "Vectorized engine").
FLOAT_DETERMINISM_SCOPE: Tuple[str, ...] = (
    "src/repro/core/",
    "src/repro/experiments/",
)

#: Trees the shared-state checker walks — the determinism scope plus the
#: concurrent serving/runtime layers (where a shared mutable default is a
#: cross-thread bug, the PR-1 class) and the analyzer itself.
SHARED_STATE_SCOPE: Tuple[str, ...] = DETERMINISM_SCOPE + (
    "src/repro/serving/",
    "src/repro/runtime/",
    "tests/",
)

#: The *declared* environment entry points: ``(repo-relative path, function
#: name)`` pairs that are allowed to read/write ``os.environ``. Everything
#: else in the determinism scope must take configuration through a spec or
#: an argument. Keep this list short — each entry is a documented knob:
#:   * ``set_smoke``/``smoke_mode`` — the ONE smoke-scale switch
#:     (benchmarks/common.py; docs/API.md);
#:   * ``_scan_enabled`` — the REPRO_FLEET_VEC_SCAN opt-in for the jitted
#:     scan path (docs/SIMULATION.md, "Vectorized engine").
#:   * ``sanitize_enabled`` — the REPRO_SANITIZE opt-in for the runtime
#:     invariant sanitizer (docs/ANALYSIS.md, "Runtime sanitizer").
#:   * ``cache_dir`` — JAX_COMPILATION_CACHE_DIR, where the entry points keep
#:     the persistent compilation cache (a deployment path; never a result).
SANCTIONED_ENVIRON: Set[Tuple[str, str]] = {
    ("src/repro/core/compile_cache.py", "cache_dir"),
    ("benchmarks/common.py", "set_smoke"),
    ("benchmarks/common.py", "smoke_mode"),
    ("src/repro/core/fleet_vec.py", "_scan_enabled"),
    ("src/repro/core/sanitize.py", "sanitize_enabled"),
    # CI output channel, not configuration: GITHUB_STEP_SUMMARY is where the
    # trend gate mirrors its markdown table — it never influences results
    ("tools/ci/check_trend.py", "_emit"),
}

#: Wall-clock readers that are fine anywhere: monotonic *interval* timers
#: used by benches and the live manager's stats. ``time.time`` /
#: ``datetime.now`` / ``time.monotonic`` are NOT here — absolute clocks
#: leak into simulated state; sanction individual live-side sites with an
#: ``allow[wall-clock]`` pragma.
SANCTIONED_TIMERS: Set[str] = {
    "perf_counter", "perf_counter_ns", "process_time", "process_time_ns",
}

#: Scenario component field -> kwargs the runtime injects when building it
#: (``run()`` passes the resolved cost model into page-cost factories), so
#: the spec checker doesn't demand them from the JSON.
SPEC_INJECTED_KWARGS = {
    "page_cost": {"cost"},
    "disruption": {"n_workers", "horizon_min"},
}

#: The declared conservation laws of the fleet engines (docs/SIMULATION.md,
#: "Counter accounting"). Every ``FleetResult`` counter must cite one; the
#: ``counter-flow`` checker fails on a counter with no law, and the runtime
#: sanitizer asserts the checkable ones per run.
COUNTER_LAWS: Dict[str, str] = {
    "service-conservation":
        "n_invocations <= n_cold + n_warm <= n_invocations + requeued "
        "(strict equality with n_invocations when requeued == 0)",
    "latency-accounting":
        "total_latency_s == sum(latency_samples_s); every sample is "
        "wait + service with wait >= 0 (Lindley nonnegativity)",
    "queue-accounting":
        "n_queued == count(queue_wait_s > 0); "
        "queue_delay_s == sum(queue_wait_s)",
    "cold-start-accounting":
        "pool_misses counts cold starts that paid an image revive; "
        "pool_misses <= n_cold + requeued",
    "cache-tier-accounting":
        "each page-model cold start hits exactly one of "
        "local | remote | miss; all tiers are zero without a page model",
    "prewarm-accounting":
        "prewarm_hits <= prewarm_spawns; dropped spawns (past the trace "
        "horizon) never become instances",
    "placement-accounting":
        "placement_warm_hits + placement_pool_hits <= service starts "
        "(n_cold + n_warm)",
    "ledger-books":
        "eviction counters only ever grow; ledger tracked bytes == "
        "sum of entry bytes at every step (sanitizer books-balance)",
    "disruption-accounting":
        "exactly one increment per applied disruption event "
        "(worker_fail / worker_recover / cache_flush)",
    "page-volume":
        "pages_transferred counts pages moved over remote + source links "
        "only (local memcpy is free)",
    "peak-tracking":
        "high-water mark: monotone under max(), equals the largest "
        "instantaneous value observed during the drain",
    "residency-accounting":
        "instance_resident_min == sum of per-instance resident windows, "
        "each clamped to the trace horizon",
}

#: ``FleetResult`` counter -> (conservation law, unified-result projection).
#: The projection is the ``MethodResult`` field the counter surfaces through
#: (dotted for dict-valued fields, e.g. ``cache_hits.local``). The
#: ``counter-flow`` checker verifies every counter here is (a) written by
#: the event engine, (b) covered by a declared law, and (c) actually
#: projected by ``scenario._method_result`` — a dropped increment or an
#: un-projected counter is a finding.
FLEET_COUNTERS: Dict[str, Tuple[str, str]] = {
    "n_invocations": ("service-conservation", "n_invocations"),
    "n_cold": ("service-conservation", "n_cold"),
    "n_warm": ("service-conservation", "n_warm"),
    "requeued": ("service-conservation", "requeued"),
    "total_latency_s": ("latency-accounting", "total_latency_s"),
    "n_queued": ("queue-accounting", "n_queued"),
    "queue_delay_s": ("queue-accounting", "queue_delay_s"),
    "pool_misses": ("cold-start-accounting", "pool_misses"),
    "cache_local_hits": ("cache-tier-accounting", "cache_hits.local"),
    "cache_remote_hits": ("cache-tier-accounting", "cache_hits.remote"),
    "cache_misses": ("cache-tier-accounting", "cache_hits.miss"),
    "prewarm_spawns": ("prewarm-accounting", "prewarm_spawns"),
    "prewarm_hits": ("prewarm-accounting", "prewarm_hits"),
    "prewarm_dropped": ("prewarm-accounting", "prewarm_dropped"),
    "placement_warm_hits": ("placement-accounting", "placement_warm_hits"),
    "placement_pool_hits": ("placement-accounting", "placement_pool_hits"),
    "evictions": ("ledger-books", "evictions"),
    "shared_cache_evictions": ("ledger-books", "shared_cache_evictions"),
    "worker_failures": ("disruption-accounting", "worker_failures"),
    "worker_recoveries": ("disruption-accounting", "worker_recoveries"),
    "cache_flushes": ("disruption-accounting", "cache_flushes"),
    "pages_transferred": ("page-volume", "pages_transferred"),
    "memory_bytes": ("peak-tracking", "memory_bytes"),
    "max_concurrent_instances": ("peak-tracking",
                                 "max_concurrent_instances"),
    "shared_cache_peak_bytes": ("peak-tracking", "shared_cache_peak_bytes"),
    "instance_resident_min": ("residency-accounting",
                              "instance_resident_min"),
}

#: ``FleetResult`` fields that are *not* counters: identity, shape echo,
#: sample arrays, and per-entity breakdowns. Writes to these need no
#: conservation law; writes to anything outside this set and
#: ``FLEET_COUNTERS`` are undeclared (a ``counter-flow`` finding).
FLEET_RESULT_STATE: Set[str] = {
    "method", "n_workers", "horizon_min",
    "latency_samples_s", "queue_wait_s", "sample_fn",
    "per_fn_latency", "per_fn_invocations", "per_worker",
}


def in_scope(rel: str, scope: Tuple[str, ...]) -> bool:
    """True when repo-relative ``rel`` lives under one of ``scope``'s trees."""
    return any(rel == s or rel.startswith(s) for s in scope)
