"""Plain reference of a dense decoder: GQA attention with rotary positions,
optional QKV bias, RMSNorm, gated SiLU MLP, tied embeddings
(``"model": "dense"``; the functions every family gives are listed in
:mod:`bench.models`).

It follows the published Qwen1.5 / Llama block and imports nothing of the
program. Two things follow the layout of the weights the program loads:

* the weights are a pytree ``{"embed": {"tok"}, "final_norm": {"scale"},
  "unit": (layer,), "rem": ()}`` whose layer leaves are stacked over depth, and
  the embedding table is padded to a multiple of 512 rows;
* a norm's stored ``scale`` is an offset: the norm multiplies by ``1 + scale``.

The reference computes in float32 with matrix products at ``HIGHEST``
precision (on a TPU the default runs float32 products in bfloat16 passes).
The same functions compute the lower-precision controls: bfloat16
throughout, or weights rounded to int8 or fp8 (:func:`quantize`).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.counts import DenseShape

VOCAB_MULTIPLE = 512
HIGHEST = jax.lax.Precision.HIGHEST


def shape_of(config: Dict) -> DenseShape:
    """Sizes and constants of a configuration in Hugging Face key names."""
    return DenseShape.from_config(config)


def arch(config: Dict, shape: DenseShape):
    """The program's ``ArchConfig`` of the model ``shape`` describes."""
    from repro.models.config import GLOBAL_ATTN, ArchConfig
    return ArchConfig(
        name=config["name"], family="dense", n_layers=shape.n_layers,
        d_model=shape.d_model, n_heads=shape.n_heads,
        n_kv_heads=shape.n_kv_heads, d_ff=shape.d_ff,
        vocab_size=shape.vocab_size, head_dim=shape.head_dim,
        attn_pattern=(GLOBAL_ATTN,), qkv_bias=shape.qkv_bias, mlp="swiglu",
        rope_theta=shape.rope_theta, norm_eps=shape.norm_eps,
        tie_embeddings=shape.tie_embeddings)


def tiny(config: Dict) -> Dict:
    """``config`` at a size a CPU test run can hold: every other key kept."""
    return dict(config, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4,
                intermediate_size=128, vocab_size=512)


def padded_vocab(shape: DenseShape) -> int:
    return -(-shape.vocab_size // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def jax_seed(seed: int, *salt: int) -> int:
    """A 31-bit key seed from a seed of any size (``PRNGKey`` would silently
    truncate one that does not fit 32 bits)."""
    return int(np.random.SeedSequence([seed, *salt]).generate_state(1)[0] >> 1)


# ---------------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------------

def _init(key, shape: DenseShape, dtype):
    d, hd, L = shape.d_model, shape.head_dim, shape.n_layers
    hq, hkv = shape.n_heads * hd, shape.n_kv_heads * hd
    ks = iter(jax.random.split(key, 16))

    def mat(*dims, fan_in):
        return jax.random.normal(next(ks), dims, jnp.float32) / math.sqrt(fan_in)

    def small(*dims, scale):
        return scale * jax.random.normal(next(ks), dims, jnp.float32)

    attn = {"wq": mat(L, d, hq, fan_in=d), "wk": mat(L, d, hkv, fan_in=d),
            "wv": mat(L, d, hkv, fan_in=d), "wo": mat(L, hq, d, fan_in=hq)}
    if shape.qkv_bias:
        attn.update(bq=small(L, hq, scale=0.2), bk=small(L, hkv, scale=0.2),
                    bv=small(L, hkv, scale=0.2))
    layer = {
        "ln1": {"scale": small(L, d, scale=0.1)},
        "attn": attn,
        "ln2": {"scale": small(L, d, scale=0.1)},
        "mlp": {"w_gate": mat(L, d, shape.d_ff, fan_in=d),
                "w_in": mat(L, d, shape.d_ff, fan_in=d),
                "w_out": mat(L, shape.d_ff, d, fan_in=shape.d_ff)},
    }
    w = {"embed": {"tok": mat(padded_vocab(shape), d, fan_in=d)},
         "final_norm": {"scale": small(d, scale=0.1)},
         "unit": (layer,), "rem": ()}
    return jax.tree.map(lambda a: a.astype(dtype), w)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _init_jit(key, shape: DenseShape, dtype):
    return _init(key, shape, dtype)


def make_weights(shape: DenseShape, seed: int, dtype: str, salt: int = 0):
    """Weights on the default device, made from ``seed`` in one jitted call,
    in ``dtype`` (``"float32"`` or ``"bfloat16"``)."""
    key = jax.random.PRNGKey(jax_seed(seed, salt))
    return _init_jit(key, shape, jnp.dtype(dtype))


# ---------------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def _rope(x, positions, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * freqs          # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]  # (S, 1, hd/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _dot(spec: str, a, b, mode: str):
    """``einsum(spec, a, b)`` in ``mode``: ``float32`` at HIGHEST precision;
    ``bfloat16``, bfloat16 operands and result."""
    if mode == "float32":
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def _layer(x, p, positions, shape: DenseShape, mode):
    B, S, _ = x.shape
    H, Hkv, hd = shape.n_heads, shape.n_kv_heads, shape.head_dim
    mm = partial(_dot, "bsd,df->bsf", mode=mode)
    eps = shape.norm_eps
    h = _rmsnorm(x, p["ln1"]["scale"], eps)
    a = p["attn"]
    q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(B, S, H, hd), positions, shape.rope_theta)
    k = _rope(k.reshape(B, S, Hkv, hd), positions, shape.rope_theta)
    v = v.reshape(B, S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=2)        # query head h reads kv head h // G
    v = jnp.repeat(v, H // Hkv, axis=2)
    scores = _dot("bqhd,bkhd->bhqk", q, k, mode).astype(jnp.float32) / math.sqrt(hd)
    causal = positions[None, :] <= positions[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = _dot("bhqk,bkhd->bqhd", probs, v, mode).astype(x.dtype)
    x = x + mm(o.reshape(B, S, H * hd), a["wo"]).astype(x.dtype)
    h = _rmsnorm(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    g = jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_in"])
    return x + mm(g.astype(x.dtype), m["w_out"]).astype(x.dtype)


def quantize(w, kind: str):
    """Weights rounded to ``kind`` (``"int8"``: symmetric, one scale per output
    channel; ``"float8_e4m3fn"``: a cast) and returned in float32. Norm
    scales and biases keep their values."""
    def q(path, a):
        key = jax.tree_util.keystr(path)
        a = a.astype(jnp.float32)
        if key.endswith(("['scale']", "['bq']", "['bk']", "['bv']")):
            return a
        if kind == "float8_e4m3fn":
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        axis = -1 if "tok" in key else -2           # rows of the table; columns
        s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
        return jnp.round(a / jnp.where(s == 0, 1, s)).clip(-127, 127) * s
    return jax.tree_util.tree_map_with_path(q, w)


@partial(jax.jit, static_argnames=("shape", "mode"))
def forward_hidden(w, tokens, shape: DenseShape, mode: str = "float32"):
    """Final normed hidden states ``(B, S, d)`` of ``tokens`` ``(B, S)``."""
    dtype = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    S = tokens.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x = jnp.take(w["embed"]["tok"], tokens, axis=0)

    def body(x, p):
        return _layer(x, p, positions, shape, mode), None

    x, _ = jax.lax.scan(body, x, w["unit"][0])
    return _rmsnorm(x, w["final_norm"]["scale"], shape.norm_eps)


@partial(jax.jit, static_argnames=("mode",))
def logits(w, hidden, mode: str = "float32"):
    """Logits over the padded vocabulary of hidden states ``(..., d)``, in
    float32."""
    tok = w["embed"]["tok"].astype(hidden.dtype)
    return _dot("...d,vd->...v", hidden, tok, mode).astype(jnp.float32)

