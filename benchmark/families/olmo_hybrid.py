"""Family ``olmo_hybrid`` (configuration files whose ``model_type`` is
``olmo_hybrid``). See ``benchmark/FAMILIES.md`` for what a family is;
this family's row of its table:

| family | program | weights | reference | operations and bytes |
|---|---|---|---|---|
| `olmo_hybrid` | `models/olmo_hybrid.py` (Gated-DeltaNet layers with a matrix state per head, one QK-normed full-attention layer in four, RMSNorm on each sub-layer's output, SwiGLU, an untied head) | `olmo_hybrid_weights.py` (one jitted call a layer kind; matrices held in bfloat16) | `olmo_hybrid_reference.py` (the token-by-token recurrence; one jitted function a layer kind, called layer by layer; attention a block of queries at a time) | `olmo_hybrid_work.py`, hand counts in `tests/test_bench_olmo_hybrid_work.py` |

``max_positions`` is the workload file's ``slot_positions`` (the model
declares 65536); ``make_reference`` also takes ``state_dtype=`` (the
bf16-state witness of ``family_control.py --state-witness``)."""
from __future__ import annotations

import json
import weakref

import jax
import numpy as np

from benchmark import olmo_hybrid_reference, olmo_hybrid_weights

seed_key = olmo_hybrid_weights.seed_key


def max_positions(shape: dict, workload: dict) -> int:
    """Positions one slot holds: the cell's, not the 65536 declared."""
    return int(workload["slot_positions"])


def program_tree(canon: dict, shape: dict) -> dict:
    """The canonical weights under ``OlmoHybridLM``'s parameter paths
    (the one place that knows them). Every array is handed over as it is
    (no copy)."""
    lin = lambda w: {"kernel": w}                       # noqa: E731
    norm = lambda g: {"scale": g}                       # noqa: E731
    tree = {"embedding": canon["embed"], "lm_head": lin(canon["head"]),
            "norm_f": norm(canon["normf_g"])}
    for l, w in enumerate(canon["layers"]):
        pre = f"layer_{l}_"
        tree[pre + "mixer_norm"] = norm(w["mixer_norm_g"])
        tree[pre + "mlp_norm"] = norm(w["mlp_norm_g"])
        tree[pre + "mlp"] = {"gate_up": lin(w["w_gate_up"]),
                             "down": lin(w["w_down"])}
        if olmo_hybrid_weights.layer_kind(shape, l) \
                == olmo_hybrid_weights.LINEAR:
            tree[pre + "gdn"] = {
                "qkv": lin(w["w_qkv"]), "gate": lin(w["w_gate"]),
                "ab": lin(w["w_ab"]), "out": lin(w["w_o"]),
                "conv_kernel": w["conv_w"], "A_log": w["A_log"],
                "dt_bias": w["dt_bias"], "o_norm": w["o_norm_g"]}
        else:
            tree[pre + "attn"] = {
                "qkv": lin(w["w_qkv"]), "out": lin(w["w_o"]),
                "q_norm": norm(w["q_norm_g"]),
                "k_norm": norm(w["k_norm_g"])}
    return tree


def config(shape: dict, positions: int, **overrides):
    from ray_lightning_tpu.models.olmo_hybrid import OlmoHybridConfig
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "rms_norm_eps", "tie_word_embeddings",
            "max_position_embeddings", "layer_types",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval")
    kw = {k: shape[k] for k in keys if k in shape}
    kw["layer_types"] = tuple(kw["layer_types"])
    return OlmoHybridConfig(max_seq_len=positions, **{**kw, **overrides})


def build(shape: dict, workload: dict, key):
    """``(model, params, facts)``: the decode-mode program and its
    weights from the key; ``facts`` are the item sizes the accepted
    GPT-2 metrics expect beside the kind's own. The program is imported
    before a weight is drawn: a tree without it fails here, at once."""
    from ray_lightning_tpu.models.olmo_hybrid import OlmoHybridLM
    model = OlmoHybridLM(config(shape, max_positions(shape, workload),
                                decode=True))
    params = program_tree(
        olmo_hybrid_weights.make_canonical(key, shape), shape)
    return model, params, {"kv_itemsize": 2, "weight_itemsize": 2}


class _Weights:
    """One canonical weight set (a dict cannot be referred to weakly)."""

    def __init__(self, tree: dict):
        self.tree = tree


#: the weight sets that references in use hold, by key and shape: the
#: float32 reference and a control or witness beside it (``serve_driver.
#: served_gaps`` builds both from one key) share one set — two are
#: 16.4 GB and fit no chip — and it is freed with the last of them
_WEIGHTS = weakref.WeakValueDictionary()


def make_reference(shape: dict, key, mode: str = "f32", **kw):
    """``f(tokens (T,), rows) -> (len(rows), V)`` teacher-forced float32
    logits from the benchmark's own weights."""
    tag = (np.asarray(jax.random.key_data(key)).tobytes(),
           json.dumps(shape, sort_keys=True))
    held = _WEIGHTS.get(tag)
    if held is None:
        held = _WEIGHTS[tag] = _Weights(
            olmo_hybrid_weights.make_canonical(key, shape))
    fn = olmo_hybrid_reference.make_logits_fn(shape, mode, **kw)
    return lambda tokens, rows: fn(held.tree, tokens, rows)
