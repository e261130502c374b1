"""Cold-start orchestration: WarmSwap vs Baseline vs Prebaking behaviour
(paper Figs. 5/6, Table 2 semantics)."""
import tempfile

import jax
import numpy as np
import pytest

from repro.core import (
    ColdStartConfig,
    ColdStartOrchestrator,
    DependencyManager,
    FunctionRegistry,
    RestorePolicy,
)
from repro.core import workloads as wl


@pytest.fixture(scope="module")
def stack():
    tmp = tempfile.mkdtemp()
    mgr = DependencyManager(disk_dir=tmp + "/pool")
    reg = FunctionRegistry(store_dir=tmp + "/store")
    mgr.register_image("py-base", "py-base", wl.py_base_builder)
    builder = wl.model_params_builder("model-tiny")
    execs = wl.make_model_executables("model-tiny")
    wl.warm_executables(execs, builder(), "model-tiny")
    mgr.register_image("model-tiny", "model-tiny", builder, executables=execs)
    for fn in ["helloworld", "pyaes", "lr_serving"]:
        w = wl.WORKLOADS[fn]
        bb = (wl.model_params_builder(w.image_id)
              if w.image_id in wl.IMAGE_CONFIGS else wl.py_base_builder)
        reg.register(fn, w.image_id, w.handler_builder, w.handler_fn,
                     base_params_builder=bb, write_baseline_checkpoint=True)
    orch = ColdStartOrchestrator(mgr, reg, ColdStartConfig())
    return mgr, reg, orch


def test_warmswap_and_baseline_agree_on_results(stack):
    """Isolation + correctness: the migrated instance computes the same answers."""
    _, reg, orch = stack
    inst_b, _ = orch.cold_start_baseline("lr_serving")
    inst_w, _ = orch.cold_start_warmswap("lr_serving")
    req = wl.WORKLOADS["lr_serving"].request_builder()
    rb, _ = inst_b.invoke(req)
    rw, _ = inst_w.invoke(req)
    assert np.array_equal(np.asarray(rb), np.asarray(rw))


def test_phase_breakdown_structure(stack):
    _, _, orch = stack
    _, tb = orch.cold_start_baseline("lr_serving")
    _, tw = orch.cold_start_warmswap("lr_serving")
    # baseline pays dependency_init; warmswap pays communication+migration instead
    assert tb.dependency_init > 0 and tb.communication == 0
    assert tw.dependency_init == 0 and tw.migration > 0
    assert tw.total < tb.total  # model-image function: WarmSwap wins (Fig. 5a)


def test_warm_start_unaffected(stack):
    """Paper Fig. 5b: warm-start latency identical across start methods."""
    _, _, orch = stack
    inst_b, _ = orch.cold_start_baseline("lr_serving")
    inst_w, _ = orch.cold_start_warmswap("lr_serving")
    req = wl.WORKLOADS["lr_serving"].request_builder()
    lat_b = min(inst_b.invoke(req)[1] for _ in range(3))
    lat_w = min(inst_w.invoke(req)[1] for _ in range(3))
    assert lat_w < 5 * lat_b + 0.05  # same order (noise-tolerant bound)


def test_prebaking_memory_scales_with_functions(stack):
    """WarmSwap pool = O(images); Prebaking = O(functions) (Fig. 7 memory)."""
    mgr, reg, orch = stack
    orch.prebake("helloworld")
    one = orch.prebaked_bytes()
    orch.prebake("pyaes")  # same image, different function
    two = orch.prebaked_bytes()
    assert two >= 2 * one * 0.9            # prebaking duplicates the base image
    pool_before = mgr.pool_bytes()
    orch.cold_start_warmswap("helloworld")
    orch.cold_start_warmswap("pyaes")
    assert mgr.pool_bytes() == pool_before  # pool unchanged: image shared


@pytest.mark.parametrize("policy", list(RestorePolicy))
def test_warmswap_restores_each_image_where_it_was_built(stack, policy):
    """A model image's parameters come back on the device; the py-base
    runtime blob, which no device code reads, stays in host memory."""
    _, _, orch = stack
    inst_m, _ = orch.cold_start_warmswap("lr_serving", policy=policy)
    inst_p, _ = orch.cold_start_warmswap("helloworld", policy=policy)
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(inst_m.params))
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(inst_p.params))


def test_prebaked_cold_start_works(stack):
    _, _, orch = stack
    orch.prebake("lr_serving")
    inst, t = orch.cold_start_prebaked("lr_serving")
    req = wl.WORKLOADS["lr_serving"].request_builder()
    r, _ = inst.invoke(req)
    assert r is not None and t.migration > 0


@pytest.mark.parametrize("policy", [RestorePolicy.BULK, RestorePolicy.LAZY,
                                    RestorePolicy.NO_PAGESERVER,
                                    RestorePolicy.NO_LAZY])
def test_all_policies_cold_start(stack, policy):
    """Table 2: every prototype variant produces a working instance."""
    _, _, orch = stack
    inst, t = orch.cold_start_warmswap("lr_serving", policy=policy)
    req = wl.WORKLOADS["lr_serving"].request_builder()
    r, _ = inst.invoke(req)
    assert r is not None
    assert t.total > 0


def test_baseline_compiles_without_persistent_cache(stack, monkeypatch):
    """Paper Fig. 3's baseline compiles from scratch: even when an entry point
    has turned JAX's persistent compilation cache on, the baseline's compile
    runs with it off (and it is on again afterwards)."""
    import jax
    _, _, orch = stack
    seen = []
    real_warm = wl.warm_executables

    def spy(execs, params, image_id):
        seen.append(jax.config.jax_enable_compilation_cache)
        real_warm(execs, params, image_id)

    monkeypatch.setattr(wl, "warm_executables", spy)
    assert jax.config.jax_enable_compilation_cache
    orch.cold_start_baseline("lr_serving")
    assert seen == [False]
    assert jax.config.jax_enable_compilation_cache
