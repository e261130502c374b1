"""Train a small LM end-to-end with the full substrate (a few hundred steps on CPU):
deterministic pipeline, AdamW + cosine schedule, async checkpoints, and an injected
mid-run failure that the supervisor rolls back transparently.

    PYTHONPATH=src python examples/train_small.py [--steps 200]
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointConfig
from repro.configs import get_config
from repro.core.compile_cache import enable_compile_cache
from repro.data import DataConfig, SyntheticTokenPipeline
from repro.models.api import make_train_step
from repro.models.transformer import init_params
from repro.optim import adamw_init
from repro.runtime import InjectedFailure, SupervisorConfig, TrainSupervisor


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = get_config("fnbench_tiny")
    data = DataConfig(global_batch=args.batch, seq_len=args.seq, seed=0)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    opt = adamw_init(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] {cfg.name}: {n/1e6:.2f}M params, {args.steps} steps")

    step_fn = jax.jit(make_train_step(cfg, peak_lr=1e-3, warmup_steps=20,
                                      total_steps=args.steps, remat="none"),
                      donate_argnums=(0, 1))
    with tempfile.TemporaryDirectory() as tmp:
        sup = TrainSupervisor(
            SupervisorConfig(checkpoint_every=50,
                             checkpoint=CheckpointConfig(tmp)),
            step_fn,
            lambda s: {k: jnp.asarray(v) for k, v in
                       SyntheticTokenPipeline.batch_at(cfg, data, s).items()})
        losses = []
        params, opt, hist = sup.run(
            params, opt, 0, args.steps,
            fail_at={args.steps // 2: InjectedFailure("simulated node failure")},
            on_metrics=lambda s, m: (
                losses.append(m["loss"]),
                print(f"[train] step {s:4d} loss={m['loss']:.4f} "
                      f"lr={m['lr']:.2e}") if s % 25 == 0 else None))
    print(f"[train] loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}; "
          f"failures recovered: {sup.restores}")
    assert hist[-1]["loss"] < hist[0]["loss"], "training must make progress"


if __name__ == "__main__":
    enable_compile_cache()
    main()
