"""Differential equivalence suite: the vectorized engine (core/fleet_vec.py)
must be BIT-identical to the discrete-event engine (core/fleet.py) — same
sha256 over the per-request latency/wait sample arrays, same counters, same
per-function and per-worker projections — across placement x capacity x
page-model x prewarm configs.  Covers:

  * every checked-in fleet scenario spec, both engines, all methods;
  * a seeded randomized-config fuzz sweep (reduced iterations under
    ``REPRO_SMOKE=1`` — the CI smoke job; tier-1 runs the full sweep);
  * the paper headline bands reproduced THROUGH the vectorized engine
    (88 % +- 5 memory saving, 2.2-3.2x dependency-loading speedup);
  * the ``jax.lax.scan`` path (``scan=True``) against the numpy solver;
  * the fast-path/fallback domain oracle (``fast_path_reason``).
"""
import glob
import hashlib
import os

import numpy as np
import pytest

from repro.core.costmodel import PAGE_COST_MODELS
from repro.core.fleet import FleetConfig, _simulate_fleet_impl
from repro.core.fleet_vec import (SCAN_STATS, _get_scan_fn, fast_path_reason,
                                  simulate_fleet_vec)
from repro.core.scenario import Scenario, run
from repro.core.simulator import CostModel
from repro.core.traces import generate_fleet_traces

SCENARIOS_DIR = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                             "scenarios")
CM = CostModel.paper_table2()

#: Reduced fuzz budget under the CI smoke job; tier-1 runs the full sweep.
N_FUZZ = 10 if os.environ.get("REPRO_SMOKE") == "1" else 32

INT_FIELDS = ("n_invocations", "n_cold", "n_warm", "n_queued", "n_workers",
              "pool_misses", "evictions", "max_concurrent_instances",
              "placement_warm_hits", "placement_pool_hits", "memory_bytes",
              "cache_local_hits", "cache_remote_hits", "cache_misses",
              "shared_cache_peak_bytes", "shared_cache_evictions",
              "pages_transferred", "prewarm_spawns", "prewarm_hits",
              "prewarm_dropped")
#: Compared EXACTLY (==, not approx): the contract is bit-identity.
FLOAT_FIELDS = ("total_latency_s", "queue_delay_s", "instance_resident_min",
                "horizon_min")


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def assert_equiv(ref, vec, label=""):
    """Bit-identity between two FleetResults (event engine vs vectorized)."""
    for name in ("latency_samples_s", "queue_wait_s", "sample_fn"):
        a, b = getattr(ref, name), getattr(vec, name)
        assert a.shape == b.shape, f"{label}: {name} shape {a.shape}!={b.shape}"
        assert _sha(a) == _sha(b), f"{label}: {name} bytes differ"
    for name in INT_FIELDS:
        assert getattr(ref, name) == getattr(vec, name), \
            f"{label}: {name} {getattr(ref, name)} != {getattr(vec, name)}"
    for name in FLOAT_FIELDS:
        assert getattr(ref, name) == getattr(vec, name), \
            f"{label}: {name} {getattr(ref, name)!r} != {getattr(vec, name)!r}"
    assert ref.per_fn_latency == vec.per_fn_latency, f"{label}: per_fn_latency"
    assert ref.per_fn_invocations == vec.per_fn_invocations, \
        f"{label}: per_fn_invocations"
    assert ref.per_worker == vec.per_worker, f"{label}: per_worker"


def check_config(traces, method, fleet_kwargs, label=""):
    """Run both engines on fresh FleetConfigs and assert bit-identity."""
    ref = _simulate_fleet_impl(traces, method, CM, FleetConfig(**fleet_kwargs))
    vec = simulate_fleet_vec(traces, method, CM, FleetConfig(**fleet_kwargs))
    assert_equiv(ref, vec, label=f"{label}/{method}")


# ---------------------------------------------------------------------------------
# Every checked-in fleet scenario, both engines, all methods
# ---------------------------------------------------------------------------------

def _fleet_spec_paths():
    out = []
    for path in sorted(glob.glob(os.path.join(SCENARIOS_DIR, "*.json"))):
        scn = Scenario.from_file(path)
        if scn.engine in ("fleet", "fleet_vec"):
            out.append(os.path.splitext(os.path.basename(path))[0])
    return out


#: Big replay specs get their horizon trimmed so tier-1 stays fast; the full
#: scale runs in the bench job (benchmarks/bench_fleet.py azure_scale cells).
_TIER1_TRIMS = {
    "azure_scale": {"traces.kwargs.horizon_min": 720},
    "azure_scale_xl": {"traces.kwargs.horizon_min": 120},
}


@pytest.mark.parametrize("name", _fleet_spec_paths())
def test_checked_in_scenarios_bit_identical(name):
    scn = Scenario.from_file(
        os.path.join(SCENARIOS_DIR, f"{name}.json")).smoke_scaled()
    overrides = dict(_TIER1_TRIMS.get(name, {}))
    # restore the full method list the smoke overrides may have trimmed
    base = Scenario.from_file(os.path.join(SCENARIOS_DIR, f"{name}.json"))
    overrides["methods"] = list(base.methods)
    ref = run(scn.with_overrides({**overrides, "engine": "fleet"}))
    vec = run(scn.with_overrides({**overrides, "engine": "fleet_vec"}))
    for method in base.methods:
        assert_equiv(ref.raw[method], vec.raw[method],
                     label=f"{name}/{method}")
    assert ref.summary == vec.summary


# ---------------------------------------------------------------------------------
# Randomized-config differential fuzz
# ---------------------------------------------------------------------------------

def _fuzz_config(case):
    """One pinned-seed random config, biased toward fast-path-eligible shapes
    but covering the fallback domain too."""
    rng = np.random.default_rng(1000 + case)
    n_fns = int(rng.integers(2, 16))
    n_images = int(rng.integers(1, min(n_fns, 4) + 1))
    traces = generate_fleet_traces(
        n_functions=n_fns,
        horizon_min=float(rng.integers(200, 1500)),
        seed=int(rng.integers(0, 1 << 16)),
        n_images=n_images,
        rate_model="zipf",
        rate_skew=float(rng.uniform(0.5, 1.5)),
        total_rate_per_min=float(rng.uniform(0.5, 12.0)),
        batched=bool(rng.integers(0, 2)),
    )
    method = ("warmswap", "prebaking", "baseline")[case % 3]
    kwargs = {
        "n_workers": int(rng.choice([1, 1, 2, 4])),
        "max_instances_per_fn": [None, 1, 2][int(rng.integers(0, 3))],
        "placement": str(rng.choice(["affinity", "affinity", "round_robin",
                                     "least_loaded"])),
        "keep_alive_min": float(rng.uniform(0.5, 25.0)),
    }
    page = str(rng.choice(["none", "none", "default", "degenerate"]))
    if page != "none":
        kwargs["page_cost"] = PAGE_COST_MODELS.build(page, cost=CM)
    if rng.integers(0, 4) == 0:
        kwargs["worker_capacity_bytes"] = int(rng.integers(1, 6)) * \
            CM.image_bytes
    if rng.integers(0, 6) == 0:
        kwargs["prewarm"] = "histogram"       # exercises the fallback branch
    return traces, method, kwargs


@pytest.mark.parametrize("case", range(N_FUZZ))
def test_fuzz_differential(case):
    traces, method, kwargs = _fuzz_config(case)
    check_config(traces, method, kwargs, label=f"fuzz{case}")


def test_fuzz_covers_both_paths():
    """The fuzz distribution must actually exercise the fast path AND the
    event-engine fallback, else the sweep proves nothing."""
    fast = fallback = 0
    for case in range(N_FUZZ):
        traces, method, kwargs = _fuzz_config(case)
        if fast_path_reason(traces, method, CM, FleetConfig(**kwargs)) is None:
            fast += 1
        else:
            fallback += 1
    assert fast >= 3 and fallback >= 3, (fast, fallback)


# ---------------------------------------------------------------------------------
# fast_path_reason: the domain oracle
# ---------------------------------------------------------------------------------

def _traces(n_fns=6, n_images=2, seed=3, horizon=500.0, rate=4.0):
    return generate_fleet_traces(n_functions=n_fns, horizon_min=horizon,
                                 seed=seed, n_images=n_images,
                                 rate_model="zipf", total_rate_per_min=rate)


def test_fast_path_domain():
    tr = _traces()
    # degenerate single-worker: in-domain
    assert fast_path_reason(tr, "warmswap", CM,
                            FleetConfig(n_workers=1,
                                        max_instances_per_fn=1)) is None
    # single worker accepts ANY placement string (routing is trivial)
    assert fast_path_reason(tr, "warmswap", CM,
                            FleetConfig(n_workers=1,
                                        placement="least_loaded")) is None
    # multi-worker affinity + sharing methods: in-domain
    assert fast_path_reason(tr, "prebaking", CM,
                            FleetConfig(n_workers=4)) is None
    # multi-worker round-robin baseline: in-domain (static rotation)
    assert fast_path_reason(tr, "baseline", CM,
                            FleetConfig(n_workers=4,
                                        placement="round_robin")) is None
    # default page model strictly favors the home worker: in-domain
    assert fast_path_reason(
        tr, "warmswap", CM,
        FleetConfig(n_workers=4,
                    page_cost=PAGE_COST_MODELS.build("default",
                                                     cost=CM))) is None


def test_fallback_domain_reasons():
    tr = _traces()
    deg_page = PAGE_COST_MODELS.build("degenerate", cost=CM)
    cases = [
        (dict(n_workers=1, prewarm="histogram"), "warmswap", "pre-warm"),
        (dict(n_workers=4, placement="least_loaded"), "warmswap", "load"),
        (dict(n_workers=4, placement="affinity"), "baseline", "load"),
        (dict(n_workers=4, page_cost=deg_page), "warmswap", "tie"),
        (dict(n_workers=2, page_cost=deg_page,
              shared_cache_bytes=CM.image_bytes), "warmswap", "cache"),
    ]
    for kwargs, method, needle in cases:
        reason = fast_path_reason(tr, method, CM, FleetConfig(**kwargs))
        assert reason is not None and needle in reason, (kwargs, reason)


def test_fast_path_reason_validation_parity():
    tr = _traces()
    with pytest.raises(ValueError, match="n_workers"):
        fast_path_reason(tr, "warmswap", CM, FleetConfig(n_workers=0))
    with pytest.raises(ValueError, match="page_cost"):
        fast_path_reason(tr, "warmswap", CM,
                         FleetConfig(shared_cache_bytes=1 << 20))
    with pytest.raises(KeyError):
        fast_path_reason(tr, "warmswap", CM, FleetConfig(placement="afinity"))


def test_fallback_configs_still_bit_identical():
    """Out-of-domain configs route through the event engine — results must
    STILL match it exactly (trivially, but the dispatch must not distort)."""
    tr = _traces()
    check_config(tr, "warmswap", dict(n_workers=4, placement="least_loaded"),
                 label="fallback-least-loaded")
    check_config(tr, "warmswap", dict(n_workers=1, prewarm="histogram"),
                 label="fallback-prewarm")


# ---------------------------------------------------------------------------------
# Paper headline bands, reproduced through the vectorized engine
# ---------------------------------------------------------------------------------

def test_headline_saving_band_via_fleet_vec():
    scn = Scenario.from_file(os.path.join(SCENARIOS_DIR, "degenerate.json"))
    res = run(scn.with_overrides({"engine": "fleet_vec"}), smoke=True)
    assert 0.83 <= res.summary["memory_saving_vs_prebaking"] <= 0.93


def test_headline_speedup_band_via_fleet_vec():
    scn = Scenario.from_file(os.path.join(SCENARIOS_DIR, "page_headline.json"))
    res = run(scn.with_overrides({"engine": "fleet_vec"}), smoke=True)
    assert 2.2 <= res.summary["dependency_loading_speedup"] <= 3.2


# ---------------------------------------------------------------------------------
# jax.lax.scan path
# ---------------------------------------------------------------------------------

def test_scan_path_bit_identical():
    tr = _traces(n_fns=8, horizon=1200.0, rate=6.0)
    for method in ("warmswap", "prebaking", "baseline"):
        cfg = dict(n_workers=1, max_instances_per_fn=1)
        ref = _simulate_fleet_impl(tr, method, CM, FleetConfig(**cfg))
        vec = simulate_fleet_vec(tr, method, CM, FleetConfig(**cfg),
                                 scan=True)
        assert SCAN_STATS["groups"] > 0, "scan path never engaged"
        assert_equiv(ref, vec, label=f"scan/{method}")


def test_scan_env_toggle(monkeypatch):
    tr = _traces(n_fns=4)
    cfg = dict(n_workers=1, max_instances_per_fn=1)
    monkeypatch.setenv("REPRO_FLEET_VEC_SCAN", "1")
    vec = simulate_fleet_vec(tr, "warmswap", CM, FleetConfig(**cfg))
    assert SCAN_STATS["groups"] > 0
    ref = _simulate_fleet_impl(tr, "warmswap", CM, FleetConfig(**cfg))
    assert_equiv(ref, vec, label="scan-env")


def test_scan_path_runs_on_cpu_device():
    """The scan path is host work: its outputs live on the CPU device, in the
    engine's float64, whatever the process's default device is."""
    import jax
    cpu = jax.devices("cpu")[0]
    tp = np.array([0.0, 0.01, 10.0, np.inf])     # minutes
    ys = _get_scan_fn()(tp, 1.0, 2.0, 1.0 / 60.0, 2.0 / 60.0, 5.0)
    assert all(y.devices() == {cpu} for y in ys)
    sample, wait = np.asarray(ys[0]), np.asarray(ys[1])
    assert sample.dtype == np.float64
    # the first arrival is cold (2 s); the second, 0.6 s later, queues
    assert sample[0] == 2.0 and wait[1] > 0
