import os
# The dry run compiles for 512 placeholder devices of the host's CPU platform.
# Pin that platform (on a chip machine JAX would otherwise default to the one
# accelerator) and add the device count to any XLA flags already set.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the appropriate step function under the production mesh
with explicit in/out shardings, compiles it (SPMD partitioning included — sharding
mismatches, compile-time OOMs, and unsupported collectives all surface here), and
records ``memory_analysis`` / ``cost_analysis`` / parsed collective bytes to a JSON
artifact for the roofline analysis.

  train_4k      -> train_step   (fwd + bwd + AdamW, donated params/opt, ZeRO-1 opt)
  prefill_32k   -> prefill_step (builds the decode state)
  decode_32k    -> serve_step   (1 new token against a seq_len KV cache, donated)
  long_500k     -> serve_step   (sub-quadratic archs only; batch=1 shards the cache
                                 sequence over 'data' — see DESIGN.md §4/§5)

Usage:
  python -m repro.launch.dryrun --arch gemma2_27b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
# NOTE: no `from __future__ import annotations` here — the environment lines above
# must stay the very first statements of the module (jax locks the platform and the
# device count on first init).
import argparse
import json
import sys
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional

import numpy as np

#: The chip the production meshes are made of (``jax.Device.device_kind`` of a
#: TPU v5e); its peaks price the roofline terms.
TARGET_DEVICE_KIND = "TPU v5 lite"


def build_cell(arch: str, shape_name: str, mesh):
    """Returns (step_fn, arg_specs (with shardings), out_shardings, donate)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, make_batch_specs
    from repro.models.api import make_prefill_step, make_serve_step, make_train_step
    from repro.models.config import SHAPES
    from repro.models.sharding import (batch_pspecs, decode_state_pspecs,
                                       mesh_axes, param_pspecs)
    from repro.models.transformer import init_decode_state, init_params
    from repro.optim import adamw_init

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    dp_axes, _ = mesh_axes(mesh)
    tp = mesh.shape["model"]
    dp = int(np.prod([mesh.shape[a] for a in dp_axes]))

    if shape_name == "long_500k" and not cfg.supports_long_context:
        raise SkipCell(f"{arch} is pure full-attention — long_500k skipped "
                       "(DESIGN.md §4)")

    key = jax.random.PRNGKey(0)
    params_abs = jax.eval_shape(lambda: init_params(key, cfg, jnp.bfloat16))
    p_specs = param_pspecs(cfg, params_abs, tp)
    ns = lambda spec: NamedSharding(mesh, spec)
    attach = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=ns(s))
    params_in = jax.tree.map(attach, params_abs, p_specs)
    p_shardings = jax.tree.map(ns, p_specs)

    if shape.kind == "train":
        data = DataConfig(global_batch=shape.global_batch, seq_len=shape.seq_len)
        bspecs = make_batch_specs(cfg, data)
        batch_abs = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in bspecs.items()}
        b_pspecs = batch_pspecs(cfg, batch_abs, dp_axes, dp)
        batch_in = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=ns(b_pspecs[k]))
                    for k, v in batch_abs.items()}
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        # ZeRO-1: shard optimizer moments over 'data' on the first unsharded,
        # divisible dim (params stay replicated over data; opt state is 4x params)
        def zero1(spec, leaf):
            dims = list(spec) + [None] * (leaf.ndim - len(spec))
            for i, (d, s) in enumerate(zip(leaf.shape, dims)):
                if s is None and d % mesh.shape["data"] == 0 and d >= mesh.shape["data"]:
                    dims[i] = "data"
                    break
            return P(*dims)
        mu_specs = jax.tree.map(zero1, p_specs, params_abs,
                                is_leaf=lambda x: isinstance(x, P))
        opt_specs = {"mu": mu_specs, "nu": mu_specs, "count": P()}
        opt_in = jax.tree.map(attach, opt_abs, opt_specs)
        opt_shardings = jax.tree.map(ns, opt_specs)
        step_in = jax.ShapeDtypeStruct((), jnp.int32, sharding=ns(P()))

        fn = make_train_step(cfg, remat="unit")
        return (fn, (params_in, opt_in, batch_in, step_in),
                (p_shardings, opt_shardings, None), (0, 1), cfg)

    if shape.kind == "prefill":
        data = DataConfig(global_batch=shape.global_batch, seq_len=shape.seq_len)
        bspecs = make_batch_specs(cfg, data)
        batch_abs = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in bspecs.items()}
        b_pspecs = batch_pspecs(cfg, batch_abs, dp_axes, dp)
        batch_in = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=ns(b_pspecs[k]))
                    for k, v in batch_abs.items()}
        state_abs = jax.eval_shape(
            lambda: init_decode_state(cfg, shape.global_batch, shape.seq_len,
                                      jnp.bfloat16))
        st_specs = decode_state_pspecs(cfg, state_abs, dp_axes, dp, tp,
                                       shape.global_batch)
        st_shardings = jax.tree.map(ns, st_specs)
        fn = make_prefill_step(cfg, state_len=shape.seq_len)
        return (fn, (params_in, batch_in), (None, st_shardings), (), cfg)

    # decode
    state_abs = jax.eval_shape(
        lambda: init_decode_state(cfg, shape.global_batch, shape.seq_len,
                                  jnp.bfloat16))
    st_specs = decode_state_pspecs(cfg, state_abs, dp_axes, dp, tp,
                                   shape.global_batch)
    state_in = jax.tree.map(attach, state_abs, st_specs)
    st_shardings = jax.tree.map(ns, st_specs)
    batch_covers = shape.global_batch % dp == 0 and shape.global_batch >= dp
    tok_spec = P(dp_axes, None) if batch_covers else P(None, None)
    token_in = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32,
                                    sharding=ns(tok_spec))
    fn = make_serve_step(cfg)
    return (fn, (params_in, state_in, token_in), (None, st_shardings), (1,), cfg)


class SkipCell(Exception):
    pass


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_path: Optional[str] = None, verbose: bool = True) -> Dict[str, Any]:
    import jax

    from repro.launch.hlo_walk import analyze_module
    from repro.launch.hlo_analysis import (cost_summary, device_peaks,
                                           memory_summary, roofline_terms)
    from repro.launch.mesh import make_production_mesh
    from repro.models.config import SHAPES

    t_start = time.perf_counter()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "chips": n_chips,
        "device_kind": TARGET_DEVICE_KIND, "status": "ok",
    }
    try:
        fn, arg_specs, out_shardings, donate, cfg = build_cell(arch, shape_name, mesh)
        with mesh:
            jitted = jax.jit(fn, out_shardings=out_shardings,
                             donate_argnums=donate)
            t0 = time.perf_counter()
            lowered = jitted.lower(*arg_specs)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()

        cost = cost_summary(compiled)              # raw XLA numbers (while-body x1!)
        mem = memory_summary(compiled)
        hlo_text = compiled.as_text()
        walk = analyze_module(hlo_text)            # trip-count-aware (see hlo_walk)
        record.update({
            "lower_s": t1 - t0, "compile_s": t2 - t1,
            "cost_raw": cost, "memory": mem, "hlo_walk": walk,
        })

        # roofline inputs (per device; cost_analysis of the SPMD module is per-device)
        shape = SHAPES[shape_name]
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        n_active = cfg.active_param_count()
        model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
        if shape.kind == "decode":
            # decode attention reads the KV cache: count 2*N*B for the matmuls only
            model_flops = 2 * n_active * shape.global_batch
        record["model_flops_global"] = float(model_flops)
        record["model_flops_per_device"] = float(model_flops / n_chips)
        rt = roofline_terms(walk["flops"], walk["bytes"],
                            walk["collective_ring_weighted_bytes"],
                            device_kind=TARGET_DEVICE_KIND)
        rt["useful_flops_ratio"] = (record["model_flops_per_device"] /
                                    max(walk["flops"], 1.0))
        rt["mfu_upper_bound"] = (record["model_flops_per_device"] /
                                 max(rt["step_lower_bound_s"], 1e-30)
                                 / device_peaks(TARGET_DEVICE_KIND)["flops"])
        record["roofline"] = rt
        if verbose:
            ma = mem.get("live_bytes", 0) / 1e9
            print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: "
                  f"compile={record['compile_s']:.1f}s "
                  f"flops/dev={walk['flops']:.3e} bytes/dev={walk['bytes']:.3e} "
                  f"coll/dev={walk['collective_ring_weighted_bytes']:.3e}B "
                  f"live={ma:.2f}GB bottleneck={rt['bottleneck']} "
                  f"useful={rt['useful_flops_ratio']:.2f}")
            print(f"[dryrun]   memory_analysis: {mem}")
    except SkipCell as e:
        record["status"] = "skipped"
        record["reason"] = str(e)
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: SKIPPED — {e}")
    except Exception as e:  # a failure here is a bug in the distribution config
        record["status"] = "failed"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: FAILED — {e}")
    record["wall_s"] = time.perf_counter() - t_start
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from repro.configs import ARCH_IDS
    from repro.models.config import SHAPES

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if not args.all:
        assert args.arch and args.shape
        rc = 0
        for mk in meshes:
            rec = run_cell(args.arch, args.shape, mk,
                           out_path=os.path.join(
                               args.out, f"{args.arch}__{args.shape}__{mk}.json"))
            rc |= int(rec["status"] == "failed")
        sys.exit(rc)

    # --all: one subprocess per cell (isolation: compile memory is reclaimed,
    # a single pathological cell cannot take down the sweep)
    import subprocess
    archs = [a for a in ARCH_IDS if a != "fnbench_tiny"]
    failures = 0
    for mk in meshes:
        for arch in archs:
            for shape_name in SHAPES:
                path = os.path.join(args.out, f"{arch}__{shape_name}__{mk}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] skip existing {path}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name, "--mesh", mk,
                       "--out", args.out]
                t0 = time.perf_counter()
                try:
                    r = subprocess.run(cmd, timeout=args.timeout)
                    rc = r.returncode
                except subprocess.TimeoutExpired:
                    rc = -1
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape_name, "mesh": mk,
                                   "status": "failed",
                                   "error": f"timeout>{args.timeout}s"}, f)
                failures += int(rc != 0)
                print(f"[sweep] {arch} x {shape_name} x {mk}: rc={rc} "
                      f"({time.perf_counter() - t0:.0f}s)")
    print(f"[sweep] done, {failures} failures")
    sys.exit(int(failures > 0))


if __name__ == "__main__":
    main()
