"""End-to-end serving driver (the paper's kind: serving) — the main example.

A provider fleet: one shared dependency image, two serving replicas brought up by
live migration, continuous-batched decode traffic, a simulated node failure, and
pool-based recovery — timed at every step.

    PYTHONPATH=src python examples/serve_e2e.py [--requests 24]
"""
import argparse
import time

import numpy as np

from repro.configs import get_reduced
from repro.core import DependencyManager, RestorePolicy
from repro.core.compile_cache import enable_compile_cache
from repro.models.transformer import init_params
from repro.runtime import ReplicaSet
from repro.serving import ServeConfig, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--arch", default="qwen3_1_7b")
    args = ap.parse_args()

    import jax, jax.numpy as jnp
    cfg = get_reduced(args.arch)
    mgr = DependencyManager()
    mgr.register_image("base", cfg.name,
                       lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.float32))
    print(f"[pool] image 'base' live: {mgr.pool_bytes()/1e6:.1f} MB")

    scfg = ServeConfig(max_slots=4, max_seq_len=128, max_new_tokens=8)

    def make_engine(manager, image_id, cfg, method):
        if method == "warmswap":
            return ServingEngine.from_pool(manager, image_id, cfg, scfg,
                                           policy=RestorePolicy.BULK)
        return ServingEngine(cfg, init_params(jax.random.PRNGKey(0), cfg,
                                              jnp.float32), scfg)

    fleet = ReplicaSet(mgr, "base", cfg, make_engine, n_replicas=2)
    for e in fleet.events:
        print(f"[fleet] {e.replica} up via {e.method} in {e.seconds:.3f}s")

    # traffic
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    names = list(fleet.replicas)
    for i in range(args.requests):
        eng = fleet.replicas[names[i % len(names)]]
        eng.submit(rng.integers(0, cfg.vocab_size, int(rng.integers(4, 32))))
    for name, eng in fleet.replicas.items():
        eng.run_until_done()
        m = eng.metrics()
        print(f"[serve] {name}: {m['completed']} done, "
              f"ttft {m['mean_ttft_s']*1e3:.0f}ms, "
              f"latency {m['mean_latency_s']*1e3:.0f}ms")
    print(f"[serve] wall: {time.perf_counter()-t0:.2f}s")

    # failure + recovery through the pool
    victim = names[0]
    print(f"[fault] killing {victim}")
    fleet.kill(victim)
    dt_warm = fleet.recover(victim, method="warmswap")
    fleet.kill(victim)
    dt_cold = fleet.recover(victim, method="baseline")
    print(f"[fault] recovery via pool: {dt_warm:.3f}s | cold reload: {dt_cold:.3f}s "
          f"-> x{dt_cold/max(dt_warm,1e-9):.1f} faster")
    eng = fleet.replicas[victim]
    eng.submit(rng.integers(0, cfg.vocab_size, 8))
    eng.run_until_done()
    print(f"[fault] recovered replica serving again: "
          f"{eng.metrics()['completed']} request(s) done")


if __name__ == "__main__":
    enable_compile_cache()
    main()
