"""Paper Fig. 7 + §4.5 case study: ten functions sharing ONE dependency image under
two-week Azure-statistics traces — average latency per invocation-rate quartile and
required warm-up memory, WarmSwap vs Prebaking vs Baseline.

Driven by the checked-in ``benchmarks/scenarios/sharing_fig7.json`` spec
(single-worker engine) through the experiments CLI's ``run_file``. Runs twice:
once with the PAPER's measured cost numbers (Table 2; the faithful simulation)
and once with the cold-start costs that ``bench_coldstart`` measured earlier in the
same ``benchmarks.run`` invocation, when it ran — the measured variant is the
same spec with its ``cost`` component overridden to ``scalar`` + measured
kwargs. Nothing is read from an earlier run's artifacts."""
from __future__ import annotations

from typing import Dict, Optional

from benchmarks.common import (emit, save_json, scenario_path, smoke_mode,
                               validated_samples)


def _measured_cost_kwargs(coldstart_rows: Dict) -> Optional[Dict]:
    """Scalar-cost-model kwargs from ``bench_coldstart``'s rows, or None when
    they hold no ``rnn_serving`` row."""
    rnn = coldstart_rows.get("rnn_serving")
    if not rnn:
        return None
    return {
        "cold_warmswap_s": rnn["cold_warmswap_s"],
        "cold_prebaking_s": rnn["cold_warmswap_s"] * 1.05,  # prebake ~ bulk restore
        "cold_baseline_s": rnn["cold_baseline_s"],
        "warm_s": rnn["warm_warmswap_s"],
    }


def run(coldstart_rows: Optional[Dict] = None) -> Dict:
    """Run the Fig. 7 spec with the paper's costs, and also with the costs in
    ``coldstart_rows`` (``bench_coldstart.run()``'s output) when given."""
    from repro.experiments import run_file

    smoke = smoke_mode()
    out: Dict = {}
    variants: Dict[str, Optional[Dict]] = {"paper_costs": None}
    measured = (_measured_cost_kwargs(coldstart_rows)
                if coldstart_rows is not None else None)
    if measured is not None:
        variants["measured_costs"] = {
            "cost.name": "scalar", "cost.kwargs": measured}

    for label, overrides in variants.items():
        result = run_file(scenario_path("sharing_fig7"), smoke=smoke,
                          overrides=overrides)
        res: Dict = {}
        for method, mr in result.methods.items():
            validated_samples(result.raw[method], f"sharing/{label}/{method}")
            res[method] = {
                "avg_latency_s": mr.avg_latency_s,
                "cold": mr.n_cold, "warm": mr.n_warm,
                "memory_mb": mr.memory_bytes / 1e6,
                "quartile_latency_s": mr.quartile_latency_s,
            }
            emit(f"sharing/{label}/{method}", mr.avg_latency_s * 1e6,
                 f"mem={mr.memory_bytes / 1e6:.0f}MB cold={mr.n_cold}")
        saving = result.summary["memory_saving_vs_prebaking"]
        speed = (res["prebaking"]["avg_latency_s"] /
                 max(res["warmswap"]["avg_latency_s"], 1e-12))
        res["memory_saving_vs_prebaking"] = saving
        res["latency_ratio_vs_prebaking"] = speed
        emit(f"sharing/{label}/headline", saving * 100,
             f"memory_saving_pct (paper: 88); warmswap x{speed:.2f} vs prebaking")
        out[label] = res
    save_json("bench_sharing", out)
    return out


if __name__ == "__main__":
    run()
