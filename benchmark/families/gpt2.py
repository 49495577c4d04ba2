"""Family ``gpt2`` (configuration files whose ``model_type`` is
``gpt2``): ``models/transformer.py`` with ``weights.py`` /
``reference.py``, exactly as ``kinds/serve_closed_loop.py`` builds them.
See ``benchmark/FAMILIES.md``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference, weights

seed_key = weights.seed_key


def max_positions(shape: dict, workload: dict) -> int:
    return int(shape["n_positions"])


def build(shape: dict, workload: dict, key):
    from ray_lightning_tpu.models.transformer import (TransformerConfig,
                                                      TransformerLM)
    n_head = shape["n_head"]
    cfg = TransformerConfig(
        vocab_size=shape["vocab_size"], max_seq_len=shape["n_positions"],
        d_model=shape["n_embd"], n_heads=n_head, n_layers=shape["n_layer"],
        d_ff=4 * shape["n_embd"], dtype=jnp.bfloat16,
        param_dtype=jnp.float32, causal=True, decode=True,
        scan_layers=False)
    params = jax.jit(lambda k: weights.program_tree(
        weights.make_canonical(k, shape), n_head, scanned=False))(key)
    return TransformerLM(cfg), params, {
        "kv_itemsize": jnp.dtype(cfg.dtype).itemsize,
        "weight_itemsize": jnp.dtype(cfg.param_dtype).itemsize}


def make_reference(shape: dict, key, mode: str = "f32"):
    """``f(tokens (T,), rows) -> (len(rows), V)`` on the host."""
    params = jax.jit(lambda k: weights.make_canonical(k, shape))(key)
    fn = reference.make_logits_fn(shape, mode)
    width = shape["n_positions"]

    def logits(tokens, rows):
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(tokens)] = tokens
        return np.asarray(fn(params, toks))[np.asarray(rows)]

    return logits
