"""Serving engine (continuous batching), fleet scheduler (stragglers), and
fault-tolerance (supervisor rollback determinism, pool-based replica recovery)."""
import gc
import tempfile
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointConfig
from repro.configs import get_reduced
from repro.core import DependencyManager, RestorePolicy
from repro.data import DataConfig, SyntheticTokenPipeline
from repro.models.api import make_train_step
from repro.models.transformer import decode_step, forward, init_params
from repro.optim import adamw_init
from repro.runtime import InjectedFailure, ReplicaSet, SupervisorConfig, TrainSupervisor
from repro.serving import FleetScheduler, SchedulerConfig, ServeConfig, ServingEngine

CFG = get_reduced("qwen3_1_7b")
PARAMS = init_params(jax.random.PRNGKey(0), CFG, jnp.float32)


def _greedy_reference(prompt, n):
    toks = jnp.asarray(prompt[None])
    logits, _, st = forward(PARAMS, toks, CFG, make_state=True, state_len=64,
                            logits_slice=1)
    seq = [int(jnp.argmax(logits[0, -1, : CFG.vocab_size]))]
    for _ in range(n - 1):
        lg, st = decode_step(PARAMS, st, jnp.asarray([[seq[-1]]], jnp.int32), CFG)
        seq.append(int(jnp.argmax(lg[0, : CFG.vocab_size])))
    return seq


def test_continuous_batching_matches_single_stream():
    eng = ServingEngine(CFG, PARAMS, ServeConfig(max_slots=3, max_seq_len=64,
                                                 max_new_tokens=5))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab_size, n) for n in (4, 9, 6, 11, 5)]
    rids = [eng.submit(p) for p in prompts]
    eng.run_until_done()
    assert len(eng.completed) == len(prompts)
    for rid, prompt in zip(rids, prompts):
        assert eng.completed[rid].tokens == _greedy_reference(prompt, 5)


def test_slot_reuse_is_clean():
    """A slot that served request A must not leak cache state into request B."""
    eng = ServingEngine(CFG, PARAMS, ServeConfig(max_slots=1, max_seq_len=64,
                                                 max_new_tokens=4))
    rng = np.random.default_rng(1)
    p1, p2 = rng.integers(0, CFG.vocab_size, 8), rng.integers(0, CFG.vocab_size, 13)
    r1 = eng.submit(p1)
    r2 = eng.submit(p2)
    eng.run_until_done()
    assert eng.completed[r1].tokens == _greedy_reference(p1, 4)
    assert eng.completed[r2].tokens == _greedy_reference(p2, 4)


def test_scheduler_straggler_redispatch():
    # quarantine_after_flags=1: after one flag the replica's EWMA keeps it from
    # being re-picked, so a second flag never arrives under healthy alternatives
    sched = FleetScheduler(SchedulerConfig(straggler_factor=2.0, min_observations=2,
                                           quarantine_after_flags=1))
    for n in ("a", "b"):
        sched.register_replica(n)
    lat = {"a": [0.01] * 4 + [0.5, 0.5, 0.01], "b": [0.012] * 12}
    idx = {"a": 0, "b": 0}

    def execute(name, item):
        v = lat[name][min(idx[name], len(lat[name]) - 1)]
        idx[name] += 1
        return v

    sched.run([object()] * 10, execute)
    assert any(e[0] == "redispatch" for e in sched.dispatch_log)
    assert sched.health["a"].quarantined           # repeated straggler quarantined
    assert sched.pick() == "b"


def test_supervisor_failure_recovery_is_deterministic():
    """With deterministic data replay, a run interrupted by failures converges to
    the SAME final params as an uninterrupted run."""
    cfg = CFG
    data = DataConfig(global_batch=2, seq_len=16, seed=5)
    step_fn = jax.jit(make_train_step(cfg, remat="none", total_steps=20))
    batch_at = lambda s: {k: jnp.asarray(v) for k, v in
                          SyntheticTokenPipeline.batch_at(cfg, data, s).items()}

    def run(fail):
        with tempfile.TemporaryDirectory() as tmp:
            sup = TrainSupervisor(
                SupervisorConfig(checkpoint_every=4,
                                 checkpoint=CheckpointConfig(tmp, async_save=False)),
                step_fn, batch_at)
            p = init_params(jax.random.PRNGKey(9), cfg, jnp.float32)
            o = adamw_init(p)
            fails = {6: InjectedFailure("node died"),
                     9: InjectedFailure("nan storm")} if fail else None
            p, o, hist = sup.run(p, o, 0, 12, fail_at=fails)
            return p, sup.restores

    p_clean, r0 = run(False)
    p_faulty, r1 = run(True)
    assert r0 == 0 and r1 == 2
    for a, b in zip(jax.tree.leaves(p_clean), jax.tree.leaves(p_faulty)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_replica_failure_pool_recovery():
    """Node-failure recovery via the dependency pool (re-warm) works and the
    replacement replica serves identical results."""
    mgr = DependencyManager()
    mgr.register_image("base", CFG.name,
                       lambda: init_params(jax.random.PRNGKey(0), CFG, jnp.float32))

    def make_engine(manager, image_id, cfg, method):
        if method == "warmswap":
            return ServingEngine.from_pool(manager, image_id, cfg,
                                           ServeConfig(max_slots=1, max_seq_len=64,
                                                       max_new_tokens=4))
        params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)  # cold load
        return ServingEngine(cfg, params, ServeConfig(max_slots=1, max_seq_len=64,
                                                      max_new_tokens=4))

    rs = ReplicaSet(mgr, "base", CFG, make_engine, n_replicas=2)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, CFG.vocab_size, 6)
    ref = _greedy_reference(prompt, 4)

    rs.kill("replica-0")
    assert "replica-0" not in rs.replicas
    dt = rs.recover("replica-0", method="warmswap")
    assert dt > 0
    eng = rs.replicas["replica-0"]
    rid = eng.submit(prompt)
    eng.run_until_done()
    assert eng.completed[rid].tokens == ref


def test_bring_up_frees_a_dropped_replica_held_in_a_cycle():
    """A replica dropped while a reference cycle holds it (a wrapper of one of
    its methods, stored on it) gives its device memory back by the next
    bring-up, even with the cyclic collector off."""
    mgr = DependencyManager()
    mgr.register_image("base", CFG.name, lambda: PARAMS)
    scfg = ServeConfig(max_slots=1, max_seq_len=32, max_new_tokens=2)
    old = ServingEngine.from_pool(mgr, "base", CFG, scfg)
    step = old.step
    old.step = lambda: step()
    gone = weakref.ref(old)
    del old, step
    gc.disable()
    try:
        new = ServingEngine.from_pool(mgr, "base", CFG, scfg)
        assert gone() is None
    finally:
        gc.enable()
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(new.params))
