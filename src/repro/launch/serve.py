"""End-to-end serving driver: WarmSwap pool -> engine bring-up -> batched requests.

This is the paper's runtime phase as a service: the provider registers dependency
images once; replicas cold-start by live migration from the pool (compile-cache +
page stream) and then serve continuous-batched decode traffic.

  python -m repro.launch.serve --image model-tiny --requests 16 --slots 4
  python -m repro.launch.serve --arch qwen3_1_7b --reduced --requests 8
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np


def start_replica(manager, image_id: str, cfg, serve_cfg, policy
                  ) -> Tuple[Any, float]:
    """Bring one serving replica up by live migration from ``manager``'s pool;
    returns ``(engine, seconds)``."""
    from repro.serving import ServingEngine
    t0 = time.perf_counter()
    engine = ServingEngine.from_pool(manager, image_id, cfg, serve_cfg,
                                     policy=policy)
    return engine, time.perf_counter() - t0


def serve_prompts(engine, prompts: Sequence[np.ndarray]) -> Dict[str, Any]:
    """Submit ``prompts``, run the engine until every one is done, and report
    what it served: the engine's metrics plus ``seconds``, ``n_tokens`` and
    each request's generated ``tokens`` in submission order."""
    rids = [engine.submit(p) for p in prompts]
    t0 = time.perf_counter()
    engine.run_until_done()
    seconds = time.perf_counter() - t0
    tokens: List[List[int]] = [list(engine.completed[r].tokens) for r in rids]
    return {**engine.metrics(), "seconds": seconds,
            "n_tokens": sum(len(t) for t in tokens), "tokens": tokens}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--image", default=None,
                    help="workload image id (model-tiny/small/medium)")
    ap.add_argument("--arch", default=None, help="or an assigned arch id (reduced)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--policy", default="bulk",
                    choices=["bulk", "lazy", "no_pageserver", "no_lazy"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.core import DependencyManager, RestorePolicy
    from repro.core import workloads as wl
    from repro.core.compile_cache import enable_compile_cache
    from repro.models.transformer import init_params
    from repro.serving import ServeConfig

    enable_compile_cache()
    policy = RestorePolicy(args.policy)
    mgr = DependencyManager()

    if args.arch:
        from repro.configs import get_config, get_reduced
        cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
        image_id = f"arch-{cfg.name}"
        mgr.register_image(
            image_id, cfg.name,
            lambda: init_params(jax.random.PRNGKey(args.seed), cfg, jnp.float32))
    else:
        image_id = args.image or "model-tiny"
        cfg = wl.IMAGE_CONFIGS[image_id]
        mgr.register_image(image_id, image_id, wl.model_params_builder(image_id))

    print(f"[serve] pool ready: {mgr.summary()['live_images']} "
          f"({mgr.pool_bytes()/1e6:.1f} MB)")

    engine, up_s = start_replica(
        mgr, image_id, cfg,
        ServeConfig(max_slots=args.slots, max_seq_len=args.max_seq,
                    max_new_tokens=args.max_new),
        policy)
    print(f"[serve] replica cold-start via WarmSwap ({policy.value}): {up_s:.3f}s")

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, min(64, args.max_seq - args.max_new))))
               for _ in range(args.requests)]
    m = serve_prompts(engine, prompts)
    dt, total_tokens = m["seconds"], m["n_tokens"]
    print(f"[serve] {m['completed']} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s); mean ttft={m['mean_ttft_s']*1e3:.0f}ms "
          f"mean latency={m['mean_latency_s']*1e3:.0f}ms")


if __name__ == "__main__":
    main()
