"""Quickstart: the WarmSwap loop in ~60 lines.

1. Provider registers a live dependency image (base model, pre-initialized once).
2. Two tenants register endpoints that share it.
3. Cold starts: Baseline (load + compile from scratch) vs WarmSwap (live migration).
4. The same comparison as a declarative scenario: one serializable spec, one
   ``run()`` (the fleet-scale API — see docs/API.md).

    PYTHONPATH=src python examples/quickstart.py
"""
import tempfile
import zlib

from repro.core import (
    ColdStartConfig,
    ColdStartOrchestrator,
    DependencyManager,
    FunctionRegistry,
    RestorePolicy,
)
from repro.core import workloads as wl
from repro.core.compile_cache import enable_compile_cache


def main() -> None:
    tmp = tempfile.mkdtemp(prefix="warmswap-quickstart-")
    manager = DependencyManager(disk_dir=f"{tmp}/pool")
    registry = FunctionRegistry(store_dir=f"{tmp}/store")

    # --- provider setup phase (paper Fig. 4b): build the shared image ONCE -------
    image_id = "model-small"
    builder = wl.model_params_builder(image_id)
    executables = wl.make_model_executables(image_id)
    wl.warm_executables(executables, builder(), image_id)   # pre-compile
    manager.register_image(image_id, image_id, builder, executables=executables)
    print(f"pool: {manager.summary()['live_images']} "
          f"({manager.pool_bytes()/1e6:.1f} MB live)")

    # --- tenants: same dependency, private handlers -------------------------------
    w = wl.WORKLOADS["cnn_serving"]
    for tenant in ("tenant-a", "tenant-b"):
        registry.register(tenant, image_id,
                          wl._head_builder(image_id,
                                           seed=zlib.crc32(tenant.encode()) % 100),
                          w.handler_fn, base_params_builder=builder,
                          write_baseline_checkpoint=True)

    orch = ColdStartOrchestrator(manager, registry,
                                 ColdStartConfig(policy=RestorePolicy.BULK))

    # --- runtime phase (paper Fig. 4c): cold starts -------------------------------
    for tenant in ("tenant-a", "tenant-b"):
        inst_b, tb = orch.cold_start_baseline(tenant)
        inst_w, tw = orch.cold_start_warmswap(tenant)
        req = w.request_builder()
        out_b, _ = inst_b.invoke(req)
        out_w, _ = inst_w.invoke(req)
        assert (out_b == out_w).all(), "migrated instance must match baseline"
        print(f"{tenant}: baseline {tb.total:.3f}s "
              f"(load {tb.dependency_load:.3f}s + compile {tb.dependency_compile:.3f}s)"
              f" | warmswap {tw.total:.3f}s (comm {tw.communication*1e3:.1f}ms + "
              f"migrate {tw.migration*1e3:.1f}ms) -> x{tb.total/tw.total:.1f}")
    print(f"image initialized {manager.stats.builds} time(s) for "
          f"{len(registry.list())} tenants")


def scenario_quickstart() -> None:
    """The scenario API in 10 lines: declare the paper's Fig. 7 comparison as
    data, run it, read the headline."""
    from repro.core import Scenario, run

    spec = Scenario(
        name="quickstart",
        engine="single",                  # the paper-faithful Fig. 7 model
        traces={"name": "azure",          # registry key + kwargs
                "kwargs": {"n_functions": 10, "horizon_min": 24 * 60}},
        cost="paper_table2",              # the paper's measured Table 2 costs
    )
    result = run(Scenario.from_json(spec.to_json()))   # specs round-trip JSON
    print(f"scenario '{spec.name}': warmswap saves "
          f"{result.summary['memory_saving_vs_prebaking'] * 100:.0f} % memory "
          f"vs prebaking (paper: 88 %)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
    scenario_quickstart()
