"""Family ``deepseek_v2`` (configuration files whose ``model_type`` is
``deepseek_v2``: DeepSeek-V2). See ``benchmark/FAMILIES.md`` for what a
family is; this family's row of its table:

| family | program | weights | reference | operations and bytes |
|---|---|---|---|---|
| `deepseek_v2` | `models/deepseek_v2.py` (multi-head latent attention over a cache of one normed latent and one rotary key a position: expanded a block of latents at a time for a piece through `ops/mla_prefill.py`'s kernel, absorbed for a decode step through `ops/mla_decode.py`'s per-row kernel; a dense SwiGLU layer, then softmax-routed experts with the group limit, scaled and not normalised, beside the shared experts — the experts *held* of one routing group, dropless, sorted and grouped by `ops/grouped_experts.py`) | `deepseek_v2_weights.py` (one jitted call a layer, an expert at a time under `lax.map`; matrices held in bfloat16; an expert's draws depend on its global number, so the eight shares of a layer add up) | `deepseek_v2_reference.py` (the published expanded form: every head's key and value made from the latents, every held expert under a mask; one jitted function a stage, called layer by layer and block by block; attention a block of queries at a time) | `deepseek_v2_work.py`, hand counts in `tests/test_bench_deepseek_v2_work.py` |

``max_positions`` is the workload file's ``slot_positions`` (the model
declares 163840). The configuration file's ``n_routed_experts`` is the
experts this chip *holds* (``published.n_routed_experts`` are the
router's outputs, ``deployment.expert_offset`` the first one held) and
its ``vocab_size`` the slice of the vocabulary: program and reference are
given the same share.

**Near ties**, as ``families/afmoe.py`` has them. Where, in any expert
layer of the reference, a *held* expert's choice lies within
:data:`NEAR_TIE` of a flip — its router logit that close to the boundary
of the top ``k`` among the groups kept, or the last group kept and the
first left out that close in their best logits where swapping them
would change the held experts taken — the position tells nothing about
precision: the program, with bfloat16 operands upstream of the router,
may take the other side, and both are the model's. The judge
(``serve_driver.served_gaps``) is handed that row flat, so its gap is 0
for the program and for a control alike. Each call of a reference notes
the count (``phase: router_margins``, beside the ``judge`` line), and the
share left out is held under :data:`MAX_NEAR_TIE_SHARE`: past it every
row is handed over with the served token far under the bar, and nothing
passes."""
from __future__ import annotations

import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import deepseek_v2_reference, deepseek_v2_weights, harness

seed_key = deepseek_v2_weights.seed_key

#: a near tie, in the reference's own router logits. The router's input
#: is an RMS-normed row, so its logits spread by 0.02 sqrt(5120) = 1.43;
#: bfloat16 operands upstream move a logit by about 1e-2 (a relative
#: 4e-3 of the hidden state after five layers), and the difference of two
#: by about 1.4e-2: the bar is three of those. The readings are in the
#: workload file's ``source.near_ties``
NEAR_TIE = 0.04
#: the share of the rows asked of the references of one run that may be
#: near ties, held from ``MIN_ROWS`` rows on
MAX_NEAR_TIE_SHARE, MIN_ROWS = 0.5, 400


def max_positions(shape: dict, workload: dict) -> int:
    """Positions one slot holds: the cell's, not the 163840 declared."""
    return int(workload["slot_positions"])


def program_tree(canon: dict, shape: dict) -> dict:
    """The canonical weights under ``DeepseekV2LM``'s parameter paths
    (the one place that knows them). Every array is handed over as it is
    (no copy)."""
    lin = lambda w: {"kernel": w}                       # noqa: E731
    norm = lambda g: {"scale": g}                       # noqa: E731
    tree = {"embedding": canon["embed"], "lm_head": lin(canon["head"]),
            "norm_f": norm(canon["normf_g"])}
    for l, w in enumerate(canon["layers"]):
        pre = f"layer_{l}_"
        tree[pre + "attn_norm"] = norm(w["attn_norm_g"])
        tree[pre + "mlp_norm"] = norm(w["mlp_norm_g"])
        tree[pre + "attn"] = {
            "q_down": lin(w["w_dq"]), "q_norm": norm(w["q_norm_g"]),
            "q_up": lin(w["w_uq"]), "kv_down": lin(w["w_dkv"]),
            "kv_norm": norm(w["kv_norm_g"]), "w_uk": w["w_uk"],
            "w_uv": w["w_uv"], "out": lin(w["w_o"])}
        if "w_router" in w:
            tree[pre + "moe"] = {
                "router": w["w_router"], "experts_gate_up": w["w_gate_up"],
                "experts_down": w["w_down"],
                "shared_gate_up": lin(w["ws_gate_up"]),
                "shared_down": lin(w["ws_down"])}
        else:
            tree[pre + "mlp"] = {"gate_up": lin(w["w_gate_up"]),
                                 "down": lin(w["w_down"])}
    return tree


def config(shape: dict, positions: int, **overrides):
    from ray_lightning_tpu.models.deepseek_v2 import DeepseekV2Config
    z = deepseek_v2_weights.sizes(shape)
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "moe_layer_freq",
            "num_attention_heads", "num_key_value_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "n_shared_experts",
            "n_group", "topk_group", "topk_method", "scoring_func",
            "norm_topk_prob", "routed_scaling_factor", "rope_theta",
            "rms_norm_eps", "max_position_embeddings",
            "tie_word_embeddings", "attention_bias")
    kw = {k: shape[k] for k in keys if k in shape}
    kw.update(n_routed_experts=z["E"], experts_held=z["held"],
              expert_offset=z["offset"], rope_factor=z["factor"],
              rope_original_max_positions=z["original"],
              rope_beta_fast=z["beta_fast"], rope_beta_slow=z["beta_slow"],
              rope_mscale_all_dim=z["mscale_all_dim"])
    if "operand_dtype" in shape:    # a rehearsal's (float32 at nano width)
        kw.update(dtype=jnp.dtype(shape["operand_dtype"]))
    return DeepseekV2Config(max_seq_len=positions, **{**kw, **overrides})


def build(shape: dict, workload: dict, key):
    """``(model, params, facts)``: the decode-mode program and its
    weights from the key; ``facts`` are the item sizes the accepted
    GPT-2 metrics expect beside the kind's own. The program is imported
    before a weight is drawn: a tree without it fails here, at once."""
    from ray_lightning_tpu.models.deepseek_v2 import DeepseekV2LM
    model = DeepseekV2LM(config(shape, max_positions(shape, workload),
                                decode=True))
    params = program_tree(deepseek_v2_weights.make_canonical(key, shape),
                          shape)
    return model, params, {"kv_itemsize": 2, "weight_itemsize": 2}


class _Weights:
    """One canonical weight set (a dict cannot be referred to weakly),
    and what its references have been asked so far."""

    def __init__(self, tree: dict):
        self.tree = tree
        self.rows = self.near = 0


#: the weight sets that references in use hold, by key and shape: the
#: float32 reference and a control or witness beside it share one set —
#: two are 12.6 GB and fit no chip beside a reference's activations —
#: and it is freed with the last
_WEIGHTS = weakref.WeakValueDictionary()


def make_reference(shape: dict, key, mode: str = "f32", **kw):
    """``f(tokens (T,), rows) -> (len(rows), V)`` teacher-forced float32
    logits from the benchmark's own weights."""
    tag = (np.asarray(jax.random.key_data(key)).tobytes(),
           json.dumps(shape, sort_keys=True))
    held = _WEIGHTS.get(tag)
    if held is None:
        held = _WEIGHTS[tag] = _Weights(
            deepseek_v2_weights.make_canonical(key, shape))
    fn = deepseek_v2_reference.make_logits_fn(shape, mode, **kw)
    judges = mode == "f32"                      # the reference proper

    def reference(tokens, rows):
        out = np.array(fn(held.tree, tokens, rows))
        margins = fn.last_margins                   # (expert layers, rows)
        near = (margins < NEAR_TIE).any(0)
        if judges:
            held.rows += len(near)
            held.near += int(near.sum())
        share = held.near / max(held.rows, 1)
        harness.note(phase="router_margins", mode=mode,
                     positions=len(near), near_tie=NEAR_TIE,
                     near_ties=int(near.sum()), left_out=bool(judges),
                     share_so_far=round(share, 4),
                     smallest=float(margins.min()) if margins.size else None)
        if judges:
            # see the module docstring: a near tie's row is handed over
            # flat; too many of them and every row fails
            out[near] = 0.0
            if held.rows >= MIN_ROWS and share > MAX_NEAR_TIE_SHARE:
                out[:] = 0.0
                nxt = np.asarray(rows) + 1
                ok = nxt < len(tokens)
                out[np.nonzero(ok)[0], np.asarray(tokens)[nxt[ok]]] = -1e30
        return out

    return reference
