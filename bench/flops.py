"""Operations and bytes of a dense decoder transformer, from its shapes.

The arithmetic follows ``repro.models.flops`` (2 x non-embedding
parameters per token, the unembedding where logits are produced, and
attention over the valid positions), computed here from the published
configuration so that no change to the program moves the yardstick.
Keys are those of the model's ``config.json``.
"""
from __future__ import annotations

from typing import Sequence


def _dims(c: dict):
    d, hd = c["hidden_size"], c["head_dim"]
    return (d, hd, c["num_attention_heads"], c["num_key_value_heads"],
            c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"])


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer's projections: q, k, v, o and the SwiGLU MLP."""
    d, hd, hq, hkv, ff, _, _ = _dims(c)
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff


def nonembedding_params(c: dict) -> int:
    d, hd, _, _, _, n_layers, _ = _dims(c)
    norms = 2 * d + (2 * hd if c.get("qk_norm", True) else 0)
    return n_layers * (layer_matmul_params(c) + norms) + d


def embedding_params(c: dict) -> int:
    d, *_, vocab = _dims(c)
    return vocab * d * (1 if c["tie_word_embeddings"] else 2)


def param_count(c: dict) -> int:
    return nonembedding_params(c) + embedding_params(c)


def attention_flops(c: dict, kv_len: int) -> int:
    """Score and weighted-sum matmuls of one query over ``kv_len`` keys, all layers."""
    _, hd, hq, _, _, n_layers, _ = _dims(c)
    return n_layers * 2 * 2 * hq * hd * kv_len


def unembed_flops(c: dict) -> int:
    d, *_, vocab = _dims(c)
    return 2 * d * vocab


def prefill_flops(c: dict, batch: int, prompt_len: int) -> int:
    """A causal prefill of ``batch`` prompts; logits at the last position only."""
    attn = sum(attention_flops(c, i + 1) for i in range(prompt_len))
    per_seq = 2 * nonembedding_params(c) * prompt_len + attn + unembed_flops(c)
    return batch * per_seq


def decode_flops(c: dict, kv_lens: Sequence[int]) -> int:
    """One decode step; ``kv_lens[b]`` is slot b's valid positions, the new one included."""
    return sum(2 * nonembedding_params(c) + attention_flops(c, n) + unembed_flops(c)
               for n in kv_lens)


def kv_bytes_per_position(c: dict) -> int:
    _, hd, _, hkv, _, n_layers, _ = _dims(c)
    return n_layers * 2 * hkv * hd * dtype_bytes(c)


def dtype_bytes(c: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[c["torch_dtype"]]


def decode_bytes(c: dict, kv_lens: Sequence[int]) -> int:
    """Least HBM traffic of one decode step: every weight once, the cached
    K/V of each slot's earlier positions, the new position written, and
    the logits written."""
    b = dtype_bytes(c)
    weights = param_count(c) * b
    kv = kv_bytes_per_position(c) * sum(kv_lens)     # n - 1 read + 1 written
    logits = len(kv_lens) * c["vocab_size"] * b
    return weights + kv + logits


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return (t_memory, "memory") if t_memory >= t_compute else (t_compute, "compute")
