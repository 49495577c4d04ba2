"""Operations and bytes the algorithm needs, from shapes alone.

These count what a GPT-2 forward/backward or decode step *requires*, never
what a program happens to do (recomputation under remat, padded prefill
rows, dead slots are not counted), so a share built on them cannot pass
100 % unless the time leaves work out.

``shape`` everywhere is the configuration file's dict with the published
GPT-2 keys ``n_layer``, ``n_embd``, ``vocab_size``.
"""
from __future__ import annotations

from typing import Iterable


def matmul_params(shape: dict, with_head: bool = True) -> int:
    """Weights that take part in a matmul per token: per layer qkv (d x 3d),
    attention out (d x d), mlp up (d x 4d) and down (4d x d) = 12 d^2; plus
    the tied head (V x d). Embedding lookups, biases and LayerNorm are not
    matmuls and are left out."""
    d, n_layer, vocab = shape["n_embd"], shape["n_layer"], shape["vocab_size"]
    n = 12 * d * d * n_layer
    return n + vocab * d if with_head else n


def train_flops_per_token(shape: dict, seq_len: int) -> float:
    """Forward + backward of one token in a causal sequence of ``seq_len``:
    6 per matmul weight, plus causal attention — forward QK^T and AV are
    2*d*T each per layer over the full square, half of it under the causal
    mask (2*d*T), times 3 for forward + backward = 6*L*d*T."""
    d, n_layer = shape["n_embd"], shape["n_layer"]
    return 6.0 * matmul_params(shape) + 6.0 * n_layer * d * seq_len


def prefill_flops(shape: dict, prompt_len: int) -> float:
    """Forward over a prompt: 2 per block weight per token, the head once
    (only the last position's logits are needed), causal attention
    sum_i 4*d*(i+1) = 2*d*P*(P+1) per layer."""
    d, n_layer, vocab = shape["n_embd"], shape["n_layer"], shape["vocab_size"]
    return (2.0 * matmul_params(shape, with_head=False) * prompt_len
            + 2.0 * vocab * d
            + 2.0 * n_layer * d * prompt_len * (prompt_len + 1))


def decode_flops(shape: dict, context_len: int) -> float:
    """One decoded token attending ``context_len`` cached positions (itself
    included): 2 per matmul weight + 4*d*context per layer."""
    d, n_layer = shape["n_embd"], shape["n_layer"]
    return 2.0 * matmul_params(shape) + 4.0 * n_layer * d * context_len


def kv_bytes_per_token(shape: dict, kv_itemsize: int) -> int:
    """K and V of one position over all layers."""
    return 2 * shape["n_layer"] * shape["n_embd"] * kv_itemsize


def decode_step_bytes(shape: dict, contexts: Iterable[int],
                      weight_itemsize: int, kv_itemsize: int) -> float:
    """Bytes one decode step must read: every matmul weight once, in the
    dtype it is held in, plus the live KV of the occupied slots."""
    live = sum(int(c) for c in contexts)
    return (matmul_params(shape) * weight_itemsize
            + live * kv_bytes_per_token(shape, kv_itemsize))
