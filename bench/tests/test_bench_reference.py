"""The plain reference against the program's own forward pass, at a reduced
size on the CPU, and the weights the harness makes in the program's layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib.counts import DenseShape
from bench.models import dense

CASES = [
    # (qkv_bias, heads, kv heads, rope theta): Qwen1.5-like MHA with bias, and
    # grouped-query attention without
    (True, 4, 4, 1e6),
    (False, 4, 2, 1e4),
]


def program_cfg(shape, theta):
    from repro.models.config import GLOBAL_ATTN, ArchConfig
    return ArchConfig(name="t", family="dense", n_layers=shape.n_layers,
                      d_model=shape.d_model, n_heads=shape.n_heads,
                      n_kv_heads=shape.n_kv_heads, d_ff=shape.d_ff,
                      vocab_size=shape.vocab_size, head_dim=shape.head_dim,
                      attn_pattern=(GLOBAL_ATTN,), qkv_bias=shape.qkv_bias,
                      rope_theta=theta)


def shape_of(bias, h, hkv):
    return DenseShape(d_model=64, n_layers=3, n_heads=h, n_kv_heads=hkv,
                      head_dim=16, d_ff=96, vocab_size=300, qkv_bias=bias)


@pytest.mark.parametrize("bias,h,hkv,theta", CASES)
def test_layout_matches_the_program(bias, h, hkv, theta):
    from repro.models.transformer import init_params
    shape = shape_of(bias, h, hkv)
    ours = dense.make_weights(shape, 1, "bfloat16")
    theirs = init_params(jax.random.PRNGKey(0), program_cfg(shape, theta),
                         jnp.bfloat16)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


@pytest.mark.parametrize("bias,h,hkv,theta", CASES)
def test_reference_matches_program_forward(bias, h, hkv, theta):
    from repro.models.transformer import forward
    shape = shape_of(bias, h, hkv)
    w = dense.make_weights(shape, 2**40 + 9, "float32")
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 300, (2, 33)),
                       jnp.int32)
    program, _, _ = forward(w, toks, program_cfg(shape, theta))
    ref = dense.logits(w, dense.forward_hidden(w, toks, shape, theta, 1e-6))
    np.testing.assert_allclose(np.asarray(program), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mode,lo,hi", [("bfloat16", 1e-3, 1e-1)])
def test_lower_precision_modes(mode, lo, hi):
    shape = shape_of(True, 4, 4)
    w = dense.make_weights(shape, 3, "float32")
    toks = jnp.asarray(np.arange(40, dtype=np.int32)[None] * 7 % 300)
    ref = np.asarray(dense.logits(w, dense.forward_hidden(w, toks, shape, 1e6, 1e-6)))
    h = dense.forward_hidden(w, toks, shape, 1e6, 1e-6, mode=mode)
    got = np.asarray(dense.logits(w, h, mode=mode))
    err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert lo < err < hi


def test_weights_follow_the_seed():
    shape = shape_of(False, 4, 2)
    a = dense.make_weights(shape, 2**33 + 5, "bfloat16")
    b = dense.make_weights(shape, 2**33 + 5, "bfloat16")
    c = dense.make_weights(shape, 5, "bfloat16")
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
    assert dense.jax_seed(2**33) != dense.jax_seed(0)


@pytest.mark.parametrize("kind", ["int8", "float8_e4m3fn"])
def test_quantize_rounds_matrices_only(kind):
    shape = shape_of(True, 4, 4)
    w = dense.make_weights(shape, 4, "float32")
    q = dense.quantize(w, kind)
    assert np.array_equal(q["unit"][0]["ln1"]["scale"], w["unit"][0]["ln1"]["scale"])
    assert np.array_equal(q["unit"][0]["attn"]["bq"], w["unit"][0]["attn"]["bq"])
    wq, qq = np.asarray(w["unit"][0]["attn"]["wq"]), np.asarray(q["unit"][0]["attn"]["wq"])
    assert not np.array_equal(wq, qq)
    assert np.abs(wq - qq).max() < 0.1 * np.abs(wq).max()
