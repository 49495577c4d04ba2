"""Family ``afmoe`` (configuration files whose ``model_type`` is
``afmoe``: Arcee's Trinity). See ``benchmark/FAMILIES.md`` for what a
family is; this family's row of its table:

| family | program | weights | reference | operations and bytes |
|---|---|---|---|---|
| `afmoe` | `models/afmoe.py` (gated grouped-query attention, QK-norm, rotary positions on the window layers only, rings of `sliding_window` positions beside one full-length K/V layer in four, sandwich RMSNorm, a dense SwiGLU layer, then sigmoid-routed experts with a choice bias and a shared expert — the experts *held* of an expert-parallel group, dropless, sorted and grouped by `ops/grouped_experts.py`) | `afmoe_weights.py` (one jitted call a layer, an expert at a time under `lax.map`; matrices held in bfloat16; an expert's draws depend on its global number, so the eight shares of a layer add up) | `afmoe_reference.py` (banded causal masks, repeated key-value heads, every held expert under a mask; **blocked to fit** beside 8.64 GB of weights: one jitted function a stage — attention, the dense MLP, the router with the shared expert, a block of 8 held experts — called layer by layer and block by block, so only one stage's weights are ever widened to float32, 906 MB; attention a block of 512 queries at a time) | `afmoe_work.py`, hand counts in `tests/test_bench_afmoe_work.py` |

``max_positions`` is the workload file's ``slot_positions`` (the model
declares 262144). The configuration file's ``num_experts`` is the
experts this chip *holds* (``published.num_experts`` are the router's
outputs, ``deployment.expert_offset`` the first one held) and its
``vocab_size`` the slice of the vocabulary: program and reference are
given the same share.

``make_reference`` also takes ``state_dtype=`` — ``family_control.py
--state-witness`` passes bfloat16 —, which this family reads as the
precision of the **router's** operands (the one product the
configuration states in float32 whatever the matmuls' precision): the
bf16-router witness.

**Near ties.** A router breaks near ties differently under rounding: the
program, with bfloat16 operands upstream of the router, may take the
5th expert where the float32 reference takes the 4th, and both top
fours are the model's. Where, in any expert layer of the reference, a
*held* expert's selection score ``s + b`` lies within
:data:`NEAR_TIE` of the boundary between the last expert taken and the
first left out, the position tells nothing about precision; the judge
(``serve_driver.served_gaps``, which reads gaps off the reference's
logits and is not this family's to edit) is handed that row flat, so
its gap is 0 for the program and for a control alike. Each call of a
reference notes the count (``phase: router_margins``, beside the
``judge`` line), and the share left out is held under
:data:`MAX_NEAR_TIE_SHARE`: past it every row is handed over with the
served token far under the bar, and nothing passes."""
from __future__ import annotations

import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import afmoe_reference, afmoe_weights, harness

seed_key = afmoe_weights.seed_key

#: a near tie, in the reference's own selection scores ``s + b``: a held
#: expert within this of the boundary of the top ``k``, in any expert
#: layer. Seven times the noise that bfloat16 operands upstream put on
#: the difference of two selection scores (a standard deviation of
#: 0.58e-3, from how often the program leaves the reference's path at
#: each distance: ``afmoe_near_ties.py``, 11562 positions of 48 requests
#: on six seeds; every gap over 0.03 sat under 1.5e-3). The readings are
#: in the workload file's ``source.near_ties``
NEAR_TIE = 4e-3
#: the share of the rows asked of the references of one run that may be
#: near ties (a third are, at the cell's depth), held from ``MIN_ROWS``
#: rows on
MAX_NEAR_TIE_SHARE, MIN_ROWS = 0.5, 400


def max_positions(shape: dict, workload: dict) -> int:
    """Positions one slot holds: the cell's, not the 262144 declared."""
    return int(workload["slot_positions"])


def program_tree(canon: dict, shape: dict) -> dict:
    """The canonical weights under ``AfmoeLM``'s parameter paths (the
    one place that knows them). Every array is handed over as it is (no
    copy)."""
    lin = lambda w: {"kernel": w}                       # noqa: E731
    norm = lambda g: {"scale": g}                       # noqa: E731
    tree = {"embedding": canon["embed"], "lm_head": lin(canon["head"]),
            "norm_f": norm(canon["normf_g"])}
    for l, w in enumerate(canon["layers"]):
        pre = f"layer_{l}_"
        for part in ("attn", "mlp"):
            for side in ("in", "out"):
                tree[f"{pre}{part}_norm_{side}"] = norm(w[f"{part}_{side}_g"])
        tree[pre + "attn"] = {
            "qkvg": lin(w["w_qkvg"]), "q_norm": norm(w["q_norm_g"]),
            "k_norm": norm(w["k_norm_g"]), "out": lin(w["w_o"])}
        if "w_router" in w:
            tree[pre + "moe"] = {
                "router": w["w_router"], "router_bias": w["router_bias"],
                "experts_gate_up": w["w_gate_up"],
                "experts_down": w["w_down"],
                "shared_gate_up": lin(w["ws_gate_up"]),
                "shared_down": lin(w["ws_down"])}
        else:
            tree[pre + "mlp"] = {"gate_up": lin(w["w_gate_up"]),
                                 "down": lin(w["w_down"])}
    return tree


def config(shape: dict, positions: int, **overrides):
    from ray_lightning_tpu.models.afmoe import AfmoeConfig
    z = afmoe_weights.sizes(shape)
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_dense_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "sliding_window",
            "num_experts_per_tok", "num_shared_experts", "score_func",
            "route_norm", "route_scale", "n_group", "topk_group",
            "rope_theta", "rms_norm_eps", "mup_enabled",
            "max_position_embeddings", "tie_word_embeddings")
    kw = {k: shape[k] for k in keys if k in shape}
    kw.update(layer_types=z["types"], num_experts=z["E"],
              experts_held=z["held"], expert_offset=z["offset"])
    if "operand_dtype" in shape:    # a rehearsal's (float32 at nano width)
        kw.update(dtype=jnp.dtype(shape["operand_dtype"]))
    return AfmoeConfig(max_seq_len=positions, **{**kw, **overrides})


def build(shape: dict, workload: dict, key):
    """``(model, params, facts)``: the decode-mode program and its
    weights from the key; ``facts`` are the item sizes the accepted
    GPT-2 metrics expect beside the kind's own. The program is imported
    before a weight is drawn: a tree without it fails here, at once."""
    from ray_lightning_tpu.models.afmoe import AfmoeLM
    model = AfmoeLM(config(shape, max_positions(shape, workload),
                           decode=True))
    params = program_tree(afmoe_weights.make_canonical(key, shape), shape)
    return model, params, {"kv_itemsize": 2, "weight_itemsize": 2}


class _Weights:
    """One canonical weight set (a dict cannot be referred to weakly),
    and what its references have been asked so far."""

    def __init__(self, tree: dict):
        self.tree = tree
        self.rows = self.near = 0


#: the weight sets that references in use hold, by key and shape: the
#: float32 reference and a control or witness beside it share one set —
#: two are 17.3 GB and fit no chip — and it is freed with the last
_WEIGHTS = weakref.WeakValueDictionary()


def make_reference(shape: dict, key, mode: str = "f32", state_dtype=None,
                   **kw):
    """``f(tokens (T,), rows) -> (len(rows), V)`` teacher-forced float32
    logits from the benchmark's own weights."""
    tag = (np.asarray(jax.random.key_data(key)).tobytes(),
           json.dumps(shape, sort_keys=True))
    held = _WEIGHTS.get(tag)
    if held is None:
        held = _WEIGHTS[tag] = _Weights(
            afmoe_weights.make_canonical(key, shape))
    if state_dtype is not None:
        kw["router_dtype"] = state_dtype
    fn = afmoe_reference.make_logits_fn(shape, mode, **kw)
    router = jnp.dtype(kw.get("router_dtype", jnp.float32)).name
    judges = mode == "f32" and router == "float32"   # the reference proper

    def reference(tokens, rows):
        out = np.array(fn(held.tree, tokens, rows))
        margins = fn.last_margins                   # (expert layers, rows)
        near = (margins < NEAR_TIE).any(0)
        if judges:
            held.rows += len(near)
            held.near += int(near.sum())
        share = held.near / max(held.rows, 1)
        harness.note(phase="router_margins", mode=mode, router=router,
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
