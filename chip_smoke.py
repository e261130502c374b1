"""Smoke run of both entry paths on one TPU chip.

    python3 chip_smoke.py

Runs from the root of a checkout, in one process, and stops at the first failure:

1. Device check: the first JAX device must be a TPU; anything else exits
   non-zero before any work is done.
2. Cold-start path (paper Figs. 3/5/6): the provider stack of the benchmarks
   (py-base plus the three model images, executables pre-compiled). Every
   FunctionBench-analogue workload starts through the baseline, WarmSwap (BULK)
   and prebaked paths, and the migrated and prebaked outputs must equal the
   baseline's. ``rnn_serving`` also starts under LAZY, NO_PAGESERVER and NO_LAZY.
3. Serving path at full published width: ``qwen1_5_0_5b`` is pooled, a replica is
   brought up from the pool (BULK) and serves a few requests; a second engine
   cold-loaded from the same parameter pytree serves the same prompts, and every
   request's greedy tokens must be identical (migration is a byte copy).

The phase times printed are one smoke run on the device named in the last line,
not benchmark numbers. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SERVE_ARCH = "qwen1_5_0_5b"
PROMPT_LENS = (16, 24, 36, 48)
MAX_NEW_TOKENS = 8
SLOTS = 2
SEED = 0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def same_output(a, b) -> bool:
    import numpy as np
    return np.array_equal(np.asarray(a), np.asarray(b))


def where_params_live(params) -> str:
    import jax
    import numpy as np
    leaf = jax.tree.leaves(params)[0]
    if isinstance(leaf, np.ndarray):
        return "host numpy (copied to HBM at every call)"
    return f"device {sorted(str(d) for d in leaf.devices())}"


def cold_start_phase(label: str) -> None:
    from benchmarks.common import build_fleet
    from repro.core import RestorePolicy
    from repro.core import workloads as wl

    mgr, reg, orch = build_fleet(list(wl.WORKLOADS))
    log(f"provider pool: {mgr.summary()['live_images']} "
        f"({mgr.pool_bytes()} bytes)")
    for fn in wl.WORKLOADS:
        req = wl.WORKLOADS[fn].request_builder()
        inst_b, t_b = orch.cold_start_baseline(fn)
        expected, _ = inst_b.invoke(req)
        starts = {"baseline": t_b}
        policies = [RestorePolicy.BULK]
        if fn == "rnn_serving":
            policies += [RestorePolicy.LAZY, RestorePolicy.NO_PAGESERVER,
                         RestorePolicy.NO_LAZY]
        for policy in policies:
            inst_w, t_w = orch.cold_start_warmswap(fn, policy)
            out, _ = inst_w.invoke(req)
            if not same_output(out, expected):
                raise AssertionError(
                    f"{fn}: warmswap/{policy.value} output differs from baseline")
            starts[f"warmswap/{policy.value}"] = t_w
        orch.prebake(fn)
        inst_p, t_p = orch.cold_start_prebaked(fn)
        out, _ = inst_p.invoke(req)
        if not same_output(out, expected):
            raise AssertionError(f"{fn}: prebaked output differs from baseline")
        starts["prebaked"] = t_p
        for path, t in starts.items():
            verdict = "reference" if path == "baseline" else "equals baseline"
            log(f"coldstart {fn} {path} ({verdict}): phase seconds, {label}: "
                f"{json.dumps(t.as_dict())}")


def serving_phase(label: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import DependencyManager, RestorePolicy
    from repro.launch.serve import serve_prompts, start_replica
    from repro.models.transformer import init_params
    from repro.serving import ServeConfig, ServingEngine

    cfg = get_config(SERVE_ARCH)
    params = init_params(jax.random.PRNGKey(SEED), cfg, jnp.float32)
    image_id = f"arch-{cfg.name}"
    mgr = DependencyManager()
    mgr.register_image(image_id, cfg.name, lambda: params)
    log(f"serve {SERVE_ARCH}: {cfg.n_layers}L d={cfg.d_model} "
        f"vocab={cfg.vocab_size}; pool image {mgr.pool_bytes()} bytes")

    scfg = ServeConfig(max_slots=SLOTS, max_seq_len=128,
                       max_new_tokens=MAX_NEW_TOKENS)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in PROMPT_LENS]

    engine, up_s = start_replica(mgr, image_id, cfg, scfg, RestorePolicy.BULK)
    log(f"serve replica up from the pool (bulk) in {up_s} s ({label}); "
        f"served parameters live in {where_params_live(engine.params)}")
    served = serve_prompts(engine, prompts)

    reference = ServingEngine(cfg, params, scfg)
    log(f"serve reference cold-loaded without the pool; its parameters live "
        f"in {where_params_live(reference.params)}")
    expected = serve_prompts(reference, prompts)

    for name, m in (("migrated", served), ("reference", expected)):
        log(f"serve {name}: {m['completed']} requests, {m['n_tokens']} tokens "
            f"in {m['seconds']} s, mean ttft {m['mean_ttft_s']} s ({label})")
    if served["completed"] != len(prompts):
        raise AssertionError(f"served {served['completed']} of {len(prompts)}")
    if served["tokens"] != expected["tokens"]:
        raise AssertionError(
            f"migrated tokens {served['tokens']} != reference "
            f"{expected['tokens']}")
    log(f"serve tokens of all {len(prompts)} requests equal the reference")


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py: no TPU: JAX's first device is "
              f"{devices[0].platform!r}; this smoke run needs a TPU chip",
              file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke.py: run it from a checkout of the repository "
              f"({ROOT / 'src' / 'repro'} is missing)", file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from repro.core.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    label = f"one smoke run on {device['kind']}"
    cold_start_phase(label)
    serving_phase(label)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
