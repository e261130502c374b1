"""The operation and byte counters against counts made by hand."""
import json
from pathlib import Path

import pytest

from bench.lib.counts import DenseShape

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def qwen():
    return DenseShape.from_config(json.loads((CONFIGS / "qwen1.5-0.5b.json").read_text()))


def medium():
    """A grouped-query block: d=512, 8 layers, 8 query and 4 kv heads of 64."""
    return DenseShape(d_model=512, n_layers=8, n_heads=8, n_kv_heads=4,
                      head_dim=64, d_ff=2048, vocab_size=8192, qkv_bias=False)


def test_qwen_parameters():
    # per layer: q,k,v,o 4 x 1024^2; MLP 3 x 1024 x 2816; biases 3 x 1024; 2 norms
    layer = 4 * 1024 * 1024 + 3 * 1024 * 2816 + 3 * 1024 + 2 * 1024
    assert qwen().layer_params() == layer == 12_850_176
    assert qwen().params() == 24 * layer + 151_936 * 1024 + 1024 == 463_987_712


def test_medium_parameters():
    # GQA: 8 query heads, 4 kv heads of 64; MLP 3 x 512 x 2048; no biases
    layer = 512 * 512 + 2 * 512 * 256 + 512 * 512 + 3 * 512 * 2048 + 2 * 512
    assert medium().params() == 8 * layer + 8192 * 512 + 512 == 35_660_288


def test_qwen_token_flops():
    matmul = 4 * 1024 * 1024 + 3 * 1024 * 2816
    ctx = 100
    want = 24 * (2 * matmul + 4 * 16 * 64 * ctx) + 2 * 1024 * 151_936
    assert qwen().token_flops(ctx, logits=True) == want
    assert qwen().token_flops(ctx, logits=False) == want - 2 * 1024 * 151_936


@pytest.mark.parametrize("n", [1, 7, 64])
def test_prefill_is_the_sum_of_its_tokens(n):
    s = medium()
    tokens = sum(s.token_flops(p + 1, logits=False) for p in range(n))
    assert s.prefill_flops(n) == tokens + 2 * 512 * 8192


def test_qwen_decode_step_bytes():
    # f32 parameters once, plus K and V of 24 layers x 16 heads x 64 per position
    per_pos = 24 * 2 * 16 * 64 * 4
    assert qwen().kv_bytes_per_position("float32") == per_pos
    assert qwen().decode_step_bytes([10, 30], "float32", "float32") == \
        463_987_712 * 4 + 40 * per_pos
