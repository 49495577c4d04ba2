"""Family ``falcon_h1`` (configuration files whose ``model_type`` is
``falcon_h1``: TII's Falcon-H1). See ``benchmark/FAMILIES.md`` for what a
family is; this family's row of its table:

| family | program | weights | reference | operations and bytes |
|---|---|---|---|---|
| `falcon_h1` | `models/falcon_h1.py` (grouped-query attention with rotary positions and a Mamba-2 mixer side by side on one normed input in every layer, summed into one residual add, then a SwiGLU; µP multipliers on every path; an untied head; the chunkwise SSD of `ops/ssd.py` with `B` and `C` grouped, a float32 state of `mamba_d_head x mamba_d_state` a head carried through chunked prefill) | `falcon_h1_weights.py` (one jitted call a layer, the embedding and the head a block of rows at a time; matrices held in bfloat16, each drawn so that its product with the multipliers on its path is a unit-variance map) | `falcon_h1_reference.py` (the token-by-token recurrence; repeated key-value heads and B / C repeated a head; one jitted function a layer, called layer by layer; attention a block of queries at a time; the head a block of columns at a time) | `falcon_h1_work.py`, hand counts in `tests/test_bench_falcon_h1_work.py` |

``max_positions`` is the workload file's ``slot_positions`` (the model
declares 262144); ``make_reference`` also takes ``state_dtype=`` (the
bf16-state witness of ``family_control.py --state-witness``)."""
from __future__ import annotations

import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import falcon_h1_reference, falcon_h1_weights

seed_key = falcon_h1_weights.seed_key


def max_positions(shape: dict, workload: dict) -> int:
    """Positions one slot holds: the cell's, not the 262144 declared."""
    return int(workload["slot_positions"])


def program_tree(canon: dict, shape: dict) -> dict:
    """The canonical weights under ``FalconH1LM``'s parameter paths (the
    one place that knows them). Every array is handed over as it is (no
    copy)."""
    lin = lambda w: {"kernel": w}                       # noqa: E731
    norm = lambda g: {"scale": g}                       # noqa: E731
    tree = {"embedding": canon["embed"], "lm_head": lin(canon["head"]),
            "norm_f": norm(canon["normf_g"])}
    for l, w in enumerate(canon["layers"]):
        pre = f"layer_{l}_"
        tree[pre + "in_norm"] = norm(w["in_norm_g"])
        tree[pre + "ff_norm"] = norm(w["ff_norm_g"])
        tree[pre + "attn"] = {"qkv": lin(w["w_qkv"]), "out": lin(w["w_o"])}
        tree[pre + "ssm"] = {
            "in_proj": lin(w["w_in"]), "out_proj": lin(w["w_out"]),
            "conv_kernel": w["conv_w"], "conv_bias": w["conv_b"],
            "A_log": w["A_log"], "dt_bias": w["dt_bias"], "D": w["D"],
            "norm": w["ssm_norm_g"]}
        tree[pre + "mlp"] = {"gate_up": lin(w["w_gate_up"]),
                             "down": lin(w["w_down"])}
    return tree


def config(shape: dict, positions: int, **overrides):
    from ray_lightning_tpu.models.falcon_h1 import FalconH1Config
    keys = [f.name for f in FalconH1Config.__dataclass_fields__.values()
            if f.name not in ("max_seq_len", "decode", "dtype",
                              "param_dtype", "state_dtype")]
    kw = {k: shape[k] for k in keys if k in shape}
    if "operand_dtype" in shape:    # a rehearsal's (float32 at nano width)
        kw.update(dtype=jnp.dtype(shape["operand_dtype"]))
    return FalconH1Config(max_seq_len=positions, **{**kw, **overrides})


def build(shape: dict, workload: dict, key):
    """``(model, params, facts)``: the decode-mode program and its
    weights from the key; ``facts`` are the item sizes the accepted
    GPT-2 metrics expect beside the kind's own. The program is imported
    before a weight is drawn: a tree without it fails here, at once."""
    from ray_lightning_tpu.models.falcon_h1 import FalconH1LM
    model = FalconH1LM(config(shape, max_positions(shape, workload),
                              decode=True))
    params = program_tree(falcon_h1_weights.make_canonical(key, shape),
                          shape)
    return model, params, {"kv_itemsize": 2, "weight_itemsize": 2}


class _Weights:
    """One canonical weight set (a dict cannot be referred to weakly)."""

    def __init__(self, tree: dict):
        self.tree = tree


#: the weight sets that references in use hold, by key and shape: the
#: float32 reference and a control or witness beside it (``serve_driver.
#: served_gaps`` builds both from one key) share one set — two are
#: 21 GB and fit no chip — and it is freed with the last of them
_WEIGHTS = weakref.WeakValueDictionary()


def make_reference(shape: dict, key, mode: str = "f32", **kw):
    """``f(tokens (T,), rows) -> (len(rows), V)`` teacher-forced float32
    logits from the benchmark's own weights."""
    tag = (np.asarray(jax.random.key_data(key)).tobytes(),
           json.dumps(shape, sort_keys=True))
    held = _WEIGHTS.get(tag)
    if held is None:
        held = _WEIGHTS[tag] = _Weights(
            falcon_h1_weights.make_canonical(key, shape))
    fn = falcon_h1_reference.make_logits_fn(shape, mode, **kw)
    return lambda tokens, rows: fn(held.tree, tokens, rows)
