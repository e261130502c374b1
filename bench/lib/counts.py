"""Operations and bytes that a dense decoder's work requires, counted from its
configuration: the yardstick for ``serve_step_roofline`` and ``serve_mfu``.

The counts are of the model's work, not of what an implementation happens to
do: the real vocabulary (no padding rows), keys up to each token's own
position, logits only where a token is produced. So they stay valid whatever
implements the step.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


@dataclass(frozen=True)
class DenseShape:
    """The sizes of a dense decoder block stack (GQA attention, gated MLP)."""
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    qkv_bias: bool
    tie_embeddings: bool = True

    @classmethod
    def from_config(cls, c: dict) -> "DenseShape":
        """From a configuration in Hugging Face key names."""
        return cls(d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim",
                                  c["hidden_size"] // c["num_attention_heads"]),
                   d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
                   qkv_bias=bool(c.get("qkv_bias", False)),
                   tie_embeddings=bool(c.get("tie_word_embeddings", True)))

    # -- parameters --------------------------------------------------------------
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd \
            + self.n_heads * hd * d
        return attn + 3 * d * self.d_ff

    def layer_params(self) -> int:
        bias = (self.n_heads + 2 * self.n_kv_heads) * self.head_dim \
            if self.qkv_bias else 0
        return self.layer_matmul_params() + bias + 2 * self.d_model

    def params(self) -> int:
        emb = self.vocab_size * self.d_model
        if not self.tie_embeddings:
            emb *= 2
        return self.n_layers * self.layer_params() + emb + self.d_model

    # -- operations ----------------------------------------------------------------
    def token_flops(self, context: int, logits: bool) -> int:
        """Forward operations of one token that attends to ``context`` keys
        (itself included); ``logits`` adds the output projection."""
        per_layer = 2 * self.layer_matmul_params() \
            + 4 * self.n_heads * self.head_dim * context
        out = 2 * self.d_model * self.vocab_size if logits else 0
        return self.n_layers * per_layer + out

    def prefill_flops(self, prompt_len: int) -> int:
        """A causal prefill of ``prompt_len`` tokens that produces the logits
        of its last position only."""
        ctx_sum = prompt_len * (prompt_len + 1) // 2
        return (self.n_layers * (2 * self.layer_matmul_params() * prompt_len
                                 + 4 * self.n_heads * self.head_dim * ctx_sum)
                + 2 * self.d_model * self.vocab_size)

    # -- bytes -------------------------------------------------------------------------
    def kv_bytes_per_position(self, kv_dtype: str) -> int:
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim \
            * DTYPE_BYTES[kv_dtype]

    def decode_step_bytes(self, contexts: Iterable[int], param_dtype: str,
                          kv_dtype: str) -> int:
        """Bytes one batched decode step must read: every parameter once, and
        the keys and values of each sequence's live positions (``contexts``
        holds each active sequence's attended length)."""
        return (self.params() * DTYPE_BYTES[param_dtype]
                + sum(contexts) * self.kv_bytes_per_position(kv_dtype))
