"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. ``BENCHMARK.json`` there names the cell's
configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``); the configuration's ``system`` names the
driver (``bench/systems/<system>.py``) and its ``model`` the model family
(``bench/models/<model>.py``), the mix's ``kind`` its generator
(``bench/traffic/<kind>.py``) and its ``loop`` how requests arrive
(``bench/loops/<loop>.py``), and every metric is read by its own file,
``bench/metrics/<metric>.py``. The limits of the correctness check are
in ``bench/limits/<cell>.json``.

Steps: check that JAX's first device is a TPU and that there are as many chips
as the cell asks for; keep JAX's compilation cache at its fixed place in the
checkout; build and warm up the cell (its set-up); measure for ``--seconds``;
read the peak device memory and the pool's footprint; free the program's state;
compare what the window produced with the plain reference; print one JSON line.
With ``--trace 0`` it holds the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window.

Exit codes: 0 after a result line (``correct`` may be false), 2 for a
missing chip or checkout, 1 for any other failure before a result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_entry(man: Dict, name: str) -> Dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in man['workloads']]})")


def metrics_of(man: Dict, cell: Dict, trace: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or with
    ``trace`` its per-layer ones (those that list the cell, or that list no
    cells and move an end-to-end metric the cell reports)."""
    e2e = [m for m in man["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in man["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def reader(name: str):
    """The module that reads metric ``name`` (``bench/metrics/<name>.py``)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's cache directory (``$JAX_COMPILATION_CACHE_DIR``, else a
    fixed directory in the checkout), with every program kept, however fast
    it compiled, so that a second run compiles nothing."""
    import jax
    from repro.core.compile_cache import enable_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _profile(trace_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def run_cell(config: Dict, mix: Dict, cell: Dict, metrics: List[Dict],
             limits: Dict[str, float], seed: int, seconds: float, trace: bool,
             *, require_tpu: bool = True, t_process: Optional[float] = None,
             controls: tuple = ()) -> Dict:
    """One run of a cell; returns the result object (and, under
    ``"control_checks"``, the numbers of each control in ``controls``)."""
    import jax
    from bench.lib import trace as tr
    from bench.lib.peaks import device_peaks
    from bench.lib.record import CompileCounter, Recorder
    from bench.models import module_of

    devs = devices_for(cell["chips"], require_tpu)
    peaks = device_peaks(devs[0].device_kind) if require_tpu else None
    rec = Recorder()
    shape = module_of(config).shape_of(config) if "model" in config else None
    system = importlib.import_module(f"bench.systems.{config['system']}")
    cellobj = system.Cell(config, mix, seed, rec)
    cellobj.setup()
    trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace else None
    if trace:
        _profile(trace_dir.name)
    setup_s = time.monotonic() - (t_process if t_process is not None else T_PROCESS)
    with CompileCounter() as cc:
        cc.active = True
        with rec.span("bench.window"):
            win = cellobj.run_window(seconds)
        cc.active = False
    reduced = events = None
    if trace:
        jax.profiler.stop_trace()
        paths = glob.glob(f"{trace_dir.name}/**/*.xplane.pb", recursive=True)
        events = tr.events_from_xplane(paths[0])
        trace_dir.cleanup()
        bounds = tr.window_of(events)
        reduced = tr.reduce_window(events, *bounds)
    stats = [d.memory_stats() or {} for d in devs]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    pool = cellobj.pool_bytes()
    cellobj.release()
    checks = cellobj.check(limits)
    run = SimpleNamespace(window=win, spans=rec.spans, compiles=cc.compiles,
                          trace=reduced, events=events, setup_s=setup_s,
                          pool_bytes=pool, config=config, mix=mix, shape=shape,
                          peaks=peaks)
    values = {}
    for m in metrics:
        v = reader(m["name"]).read(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = all(c.ok for c in checks) and win.failed == 0 \
        and cellobj.failures.count == 0
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(win.invocations),
           "failed": win.failed, "metrics": values, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    if controls:
        out["control_checks"] = {
            k: {c.name: c.value for c in cellobj.check(limits, control=k)}
            for k in controls}
    return out


def load_cell(name: str):
    man = manifest()
    cell = cell_entry(man, name)
    confs = {c["name"]: c for c in man["configs"]}
    config = load_json(ROOT / confs[cell["config"]]["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{name}.json")
    return man, cell, config, mix, limits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench/run.py: no program at {ROOT / 'src' / 'repro'}; run it "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    man, cell, config, mix, limits = load_cell(args.workload)
    try:
        devices_for(cell["chips"], require_tpu=True)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    out = run_cell(config, mix, cell, metrics_of(man, cell, bool(args.trace)),
                   limits, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
