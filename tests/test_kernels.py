"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel bodies faithfully on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    attention_ref,
    decode_attention,
    decode_attention_ref,
    diag_recurrence,
    diag_recurrence_ref,
    flash_attention,
    page_gather,
    page_gather_ref,
)

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,S,d,causal,window,cap",
    [
        (2, 4, 2, 256, 64, True, None, None),     # GQA causal
        (1, 4, 4, 128, 64, True, 64, None),       # sliding window
        (2, 2, 1, 200, 32, True, None, 50.0),     # MQA + softcap, ragged seq
        (1, 2, 2, 96, 128, False, None, None),    # non-causal (encoder)
        (1, 8, 2, 320, 64, True, 100, 30.0),      # window + softcap combined
    ],
)
def test_flash_attention_sweep(B, H, Hkv, S, d, causal, window, cap, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, d), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, d), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=_tol(dtype),
                               rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,S,d,cap",
    [(2, 4, 2, 300, 64, None), (1, 8, 1, 512, 128, 50.0), (4, 2, 2, 64, 32, None)],
)
def test_decode_attention_sweep(B, H, Hkv, S, d, cap, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, d), dtype)
    kc = jax.random.normal(ks[1], (B, Hkv, S, d), dtype)
    vc = jax.random.normal(ks[2], (B, Hkv, S, d), dtype)
    valid = jax.random.bernoulli(ks[3], 0.7, (S,)).at[0].set(True)
    out = decode_attention(q, kc, vc, valid, softcap=cap, block_k=128,
                           interpret=True)
    ref = decode_attention_ref(q, kc, vc, valid, softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=_tol(dtype),
                               rtol=_tol(dtype))


@pytest.mark.parametrize("B,S,C,chunk,block_c",
                         [(2, 100, 64, 32, 64), (1, 256, 32, 64, 16),
                          (3, 17, 130, 8, 64), (1, 64, 2048, 16, 512)])
def test_diag_recurrence_sweep(B, S, C, chunk, block_c):
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (B, S, C), jnp.float32, 0.5, 1.0)
    b = jax.random.normal(ks[1], (B, S, C), jnp.float32)
    h0 = jax.random.normal(ks[2], (B, C), jnp.float32)
    ha, hf = diag_recurrence(a, b, h0, chunk=chunk, block_c=block_c,
                             interpret=True)
    ra, rf = diag_recurrence_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(ha), np.asarray(ra), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(rf), atol=1e-4, rtol=1e-4)


def test_diag_recurrence_matches_model_scan():
    """The kernel agrees with the model's chunked associative scan too."""
    from repro.models.recurrence import chunked_diag_recurrence
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (2, 50, 24), jnp.float32, 0.3, 1.0)
    b = jax.random.normal(ks[1], (2, 50, 24))
    h0 = jax.random.normal(ks[2], (2, 24))
    ka, kf = diag_recurrence(a, b, h0, chunk=16, block_c=24, interpret=True)
    ma, mf = chunked_diag_recurrence(a, b, h0, chunk=16)
    np.testing.assert_allclose(np.asarray(ka), np.asarray(ma), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(kf), np.asarray(mf), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
@pytest.mark.parametrize("P,R,K", [(64, 2, 20), (16, 1, 16), (8, 4, 1)])
def test_page_gather_sweep(P, R, K, dtype):
    pool = (jax.random.normal(KEY, (P, R, 128)) * 10).astype(dtype)
    ids = jax.random.randint(KEY, (K,), 0, P)
    out = page_gather(pool, ids, interpret=True)
    ref = page_gather_ref(pool, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_flash_attention_matches_model_blockwise():
    """Kernel semantics == the model's jnp blockwise path (the serving oracle)."""
    from repro.models.attention import blockwise_attention
    B, H, Hkv, S, d = 2, 4, 2, 160, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, d))
    k = jax.random.normal(ks[1], (B, Hkv, S, d))
    v = jax.random.normal(ks[2], (B, Hkv, S, d))
    out_kernel = flash_attention(q, k, v, causal=True, window=48, block_q=32,
                                 block_k=32, interpret=True)
    pos = jnp.arange(S, dtype=jnp.int32)
    out_model = blockwise_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        q_positions=pos, k_positions=pos, causal=True, window=48,
        attn_softcap=None, q_chunk=64).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("call", [
    lambda: flash_attention(*(jnp.zeros((1, 1, 8, 8)),) * 3),
    lambda: decode_attention(jnp.zeros((1, 1, 8)), jnp.zeros((1, 1, 8, 8)),
                             jnp.zeros((1, 1, 8, 8)), jnp.ones((8,), bool)),
    lambda: diag_recurrence(jnp.ones((1, 8, 8)), jnp.ones((1, 8, 8)),
                            jnp.zeros((1, 8))),
    lambda: page_gather(jnp.zeros((2, 1, 128)), jnp.zeros((1,), jnp.int32)),
], ids=["flash_attention", "decode_attention", "diag_recurrence", "page_gather"])
def test_wrapper_off_tpu_without_interpret_raises(call):
    """No wrapper falls back to the interpreter by itself: off-TPU, a call
    without ``interpret=True`` is an error."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="interpret=True"):
        call()


# ---------------------------------------------------------------------------------
# Persistent compilation cache helper (the entry points' one cache location)
# ---------------------------------------------------------------------------------

def test_compile_cache_dir_env_else_repo_path(monkeypatch, tmp_path):
    import pathlib

    from repro.core import compile_cache as cc
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert cc.cache_dir() == str(repo / ".jax_cache")
    assert cc.cache_dir() == cc.cache_dir()        # fixed: no pid, time, temp name


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_compile_cache_toggle_takes_effect(monkeypatch, tmp_path,
                                           restore_cache_config):
    """enable_compile_cache writes compiled programs to the env-named
    directory; inside persistent_cache_off nothing is written, and the cache
    works again after the block."""
    from repro.core import compile_cache as cc
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    def entries():
        return len([p for p in tmp_path.rglob("*") if p.is_file()])

    def compile_fresh(c):
        x = jnp.arange(7.0 + c)                  # fresh shape: a new program
        jax.block_until_ready(jax.jit(lambda v: v * c + 1.0)(x))

    compile_fresh(3.0)
    n_on = entries()
    assert n_on > 0
    with cc.persistent_cache_off():
        compile_fresh(5.0)
    assert entries() == n_on
    compile_fresh(11.0)
    assert entries() > n_on
