"""Policy benchmarks: the prewarm x placement tournament vs the hindsight
oracle, the per-spec oracle-dominance audit, and (full scale only) paper
Table 2's live restore prototypes.

Three parts, all sharing the canonical validated-cell path
(``benchmarks/common.scenario_cell``) so CI checks their samples like every
other simulation bench:

  * **tournament** — every registered prewarm x placement combination over
    ``benchmarks/scenarios/tournament.json`` (``experiments/tournament.py``
    through the resumable sweep executor), each cell scored on P99 latency /
    byte-minutes / cold starts plus its oracle gap, Pareto front marked.
  * **oracle-gap audit** — every checked-in fleet-engine scenario spec
    (disruption specs included) re-run at smoke scale with the hindsight
    floor (``core/oracle.py``) priced on the *same* trace objects; the
    per-method gaps land in the artifact and ``tools/ci/check_bench.py``
    fails the build on any negative or non-finite gap (the dominance
    invariant). Specs beyond ``AUDIT_MAX_ARRIVALS`` are listed as skipped —
    never silently dropped — and stay covered by the shrunken-grid
    dominance sweep in ``tests/test_oracle_properties.py``.
  * **table2** (full scale only) — the live bulk/lazy/no-pageserver/no-lazy
    restore prototypes over the three dependency-heavy serving functions;
    skipped under ``--smoke`` (the JAX model stack dwarfs the CI budget).

The ``oracle_gap`` block this bench returns is surfaced as a headline in
``results/BENCH_smoke.json`` by ``benchmarks/run.py``.
"""
from __future__ import annotations

import os
from glob import glob
from typing import Dict, List, Tuple

from benchmarks.common import (SCENARIOS_DIR, emit, median, save_json,
                               scenario_cell, scenario_path, smoke_mode)

FUNCTIONS = ["lr_serving", "cnn_serving", "rnn_serving"]
ITERS = 3

#: Audit cap: fleet specs whose (smoke-scaled) traces exceed this many
#: arrivals are reported as skipped in the artifact instead of re-simulated
#: here (the azure_scale pair's smoke overrides keep million-request traces).
AUDIT_MAX_ARRIVALS = 200_000


def _run_tournament(smoke: bool) -> Tuple[Dict, Dict]:
    """The prewarm x placement tournament over the checked-in spec; returns
    ``(tournament_report_dict, base_cell)``."""
    from repro.core.scenario import Scenario
    from repro.experiments import run_file
    from repro.experiments.tournament import run_tournament

    path = scenario_path("tournament")
    base_cell = scenario_cell(run_file(path, smoke=smoke),
                              "tournament_base", prefix="policies")
    rep = run_tournament(Scenario.from_file(path), smoke=smoke)
    for c in rep.cells:
        emit(f"policies/tournament/{c.method}/{c.prewarm}/{c.placement}",
             c.p99_s * 1e6,
             f"gap={c.oracle_gap_total_s:.3f}s "
             f"bytemin={c.byte_minutes / 1e9:.2f}GBmin cold={c.n_cold}"
             f"{' pareto' if c.pareto else ''}")
    return rep.to_dict(), base_cell


def _oracle_gap_audit(smoke: bool) -> Tuple[Dict, Dict]:
    """Dominance audit over every checked-in fleet-engine scenario spec:
    engine result vs hindsight floor on shared trace objects. Returns
    ``(per_spec_gaps, skipped)``."""
    from repro.core.oracle import gap_report, oracle_from_scenario
    from repro.core.scenario import RunOverrides, Scenario, run
    from repro.core.trace_stream import TraceStream
    from repro.core.traces import TRACE_GENERATORS

    per_spec: Dict = {}
    skipped: Dict = {}
    for path in sorted(glob(os.path.join(SCENARIOS_DIR, "*.json"))):
        scn = Scenario.from_file(path)
        if scn.engine == "single":
            continue                   # no fleet policies to dominate
        eff = scn.smoke_scaled() if smoke else scn
        traces = TRACE_GENERATORS.build(eff.traces.name, **eff.traces.kwargs)
        if isinstance(traces, TraceStream):
            # the audit shares one trace-object list between engine and
            # oracle; stream/materialized runs are bit-identical by contract
            # (docs/TRACES.md), so materializing changes nothing it measures
            st, traces = traces, traces.materialize()
            if hasattr(st, "close"):
                st.close()
        n = sum(len(t.arrivals_min) for t in traces)
        if n > AUDIT_MAX_ARRIVALS:
            skipped[eff.name] = n
            emit(f"policies/oracle_audit/{eff.name}", 0.0,
                 f"skipped: {n} arrivals > cap {AUDIT_MAX_ARRIVALS} "
                 f"(covered by tests/test_oracle_properties.py)")
            continue
        result = run(eff, overrides=RunOverrides(traces=traces))
        oracles = oracle_from_scenario(eff, traces=traces)
        per_spec[eff.name] = {}
        for m, raw in result.raw.items():
            g = gap_report(oracles[m], raw)
            per_spec[eff.name][m] = g
            emit(f"policies/oracle_audit/{eff.name}/{m}",
                 g["total_gap_s"] * 1e6,
                 f"p99_gap={g['p99_gap_s'] * 1e3:.2f}ms "
                 f"oracle_total={g['oracle_total_s']:.2f}s")
    return per_spec, skipped


def _gap_headline(tournament: Dict, per_spec: Dict, skipped: Dict) -> Dict:
    """The ``oracle_gap`` block ``check_bench`` gates: global minima over
    every tournament cell and every audited spec x method."""
    gaps_total: List[float] = []
    gaps_p99: List[float] = []
    for c in tournament["cells"]:
        gaps_total.append(c["oracle_gap_total_s"])
        gaps_p99.append(c["oracle_gap_p99_s"])
    for methods in per_spec.values():
        for g in methods.values():
            gaps_total.append(g["total_gap_s"])
            gaps_p99.append(g["p99_gap_s"])
    return {
        "min_total_gap_s": min(gaps_total),
        "min_p99_gap_s": min(gaps_p99),
        "n_cells": len(gaps_total),
        "tournament": tournament["min_gaps"],
        "specs": per_spec,
        "skipped_specs": skipped,
    }


def _run_table2() -> Dict:
    """Paper Table 2: cold/warm starts across the four restore prototypes
    (bulk restore, lazy restore, w/o page server, w/o lazy migration) for
    the three dependency-heavy serving functions — live engines, full scale
    only."""
    from benchmarks.common import build_fleet
    from repro.core import RestorePolicy
    from repro.core import workloads as wl

    mgr, reg, orch = build_fleet()
    rows: Dict = {}
    for policy in [RestorePolicy.BULK, RestorePolicy.LAZY,
                   RestorePolicy.NO_PAGESERVER, RestorePolicy.NO_LAZY]:
        rows[policy.value] = {}
        for fn in FUNCTIONS:
            cold, warm = [], []
            stats = None
            for _ in range(ITERS):
                inst, t = orch.cold_start_warmswap(fn, policy=policy)
                cold.append(t.total)
                req = wl.WORKLOADS[fn].request_builder()
                warm.append(min(inst.invoke(req)[1] for _ in range(3)))
                stats = getattr(inst, "migration_stats", None)
            rows[policy.value][fn] = {
                "cold_s": median(cold),
                "warm_s": median(warm),
                "pages": getattr(stats, "pages_transferred", None),
                "requests": getattr(stats, "requests", None),
            }
            emit(f"policy/{policy.value}/{fn}", median(cold) * 1e6,
                 f"warm={median(warm)*1e6:.0f}us pages="
                 f"{rows[policy.value][fn]['pages']}")
    return rows


def run() -> Dict:
    smoke = smoke_mode()
    tournament, base_cell = _run_tournament(smoke)
    per_spec, skipped = _oracle_gap_audit(smoke)
    out: Dict = {
        "tournament_base": base_cell,
        "tournament": tournament,
        "oracle_gap": _gap_headline(tournament, per_spec, skipped),
    }
    if not smoke:
        out["table2"] = _run_table2()
    save_json("bench_policies", out)
    return out


if __name__ == "__main__":
    run()
