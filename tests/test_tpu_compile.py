"""Compile the main path's kernels and an image executable for a described TPU
v5e, at real widths. Nothing runs: the TPU compiler refuses here what the chip
would refuse (block tiling, unsupported lowerings, VMEM), at no chip time.

The topology is described inside a module fixture, never at import, so every
test worker collects the same tests and only the worker running this file loads
the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import workloads as wl
from repro.core.compile_cache import persistent_cache_off
from repro.core.pages import DEFAULT_PAGE_SIZE
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.diag_recurrence.kernel import diag_recurrence_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.page_gather.kernel import page_gather_pallas
from repro.launch.hlo_analysis import device_peaks, roofline_terms


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without the chip
    with persistent_cache_off():
        yield


def _kernel_case(name, spec):
    """(callable, argument shapes) of one kernel at a width the system serves."""
    qwen = get_config("qwen1_5_0_5b")         # the serving path's model
    H, d = qwen.n_heads, qwen.head_dim
    if name == "flash_attention":             # a 2k-token prefill
        qkv = spec((1, H, 2048, d), jnp.bfloat16)
        return (lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
                (qkv, qkv, qkv))
    if name == "decode_attention":            # 4 slots over a 2k KV cache
        cache = spec((4, H, 2048, d), jnp.bfloat16)
        return (decode_attention_pallas,
                (spec((4, H, d), jnp.bfloat16), cache, cache,
                 spec((2048,), jnp.bool_)))
    if name == "diag_recurrence":             # falcon-mamba: d_inner x ssm_state
        mamba = get_config("falcon_mamba_7b")
        C = mamba.d_inner * mamba.ssm_state
        ab = spec((1, 2048, C), jnp.float32)
        return (lambda a, b, h0: diag_recurrence_pallas(a, b, h0),
                (ab, ab, spec((1, C), jnp.float32)))
    if name == "page_gather":                 # 4 MiB uint8 pages, 2 GB pool
        pool = spec((512, DEFAULT_PAGE_SIZE // 128, 128), jnp.uint8)
        return page_gather_pallas, (pool, spec((455,), jnp.int32))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "diag_recurrence", "page_gather"])
def test_kernel_compiles_for_v5e(name, one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn, args = _kernel_case(name, spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_prefill_logits_compiles_for_v5e(one_chip):
    """model-medium's image executable at the serving request shape."""
    image_id = "model-medium"
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(wl.model_params_builder(image_id)))
    tokens = jax.ShapeDtypeStruct((wl.SERVE_BATCH, wl.SERVE_SEQ), jnp.int32,
                                  sharding=one_chip)
    execs = wl.make_model_executables(image_id)
    compiled = execs["prefill_logits"].lower(params, tokens).compile()
    vp = compiled.out_info.shape[-1]
    assert compiled.out_info.shape == (wl.SERVE_BATCH, vp)
    assert vp >= wl.IMAGE_CONFIGS[image_id].vocab_size


def test_described_v5e_has_published_peaks(topo):
    """The roofline table is keyed by the device kind JAX reports for a v5e."""
    peaks = device_peaks(topo.devices[0].device_kind)
    assert peaks["flops"] == 197e12 and peaks["hbm_bw"] == 819e9


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(ValueError, match="no peak rates"):
        roofline_terms(1.0, 1.0, 0.0, device_kind="cpu")
