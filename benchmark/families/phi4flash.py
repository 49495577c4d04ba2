"""Family ``phi4flash`` (configuration files whose ``model_type`` is
``phi4flash``): SambaY, ``ray_lightning_tpu/models/sambay.py``. See
``benchmark/FAMILIES.md`` for what a family is."""
from __future__ import annotations

from benchmark import sambay_reference, sambay_weights

seed_key = sambay_weights.seed_key


def max_positions(shape: dict, workload: dict) -> int:
    """Positions one slot holds: the cell's, not the 262144 declared."""
    return int(workload["slot_positions"])


def program_tree(canon: dict, shape: dict) -> dict:
    """The canonical weights under ``SambaYLM``'s parameter paths (the
    one place that knows them). Matrices are handed over as they are
    (no copy); ``A_log`` is laid out ``(N, di)`` as the program holds
    its state."""
    lin = lambda w, b=None: ({"kernel": w} if b is None       # noqa: E731
                             else {"kernel": w, "bias": b})
    diff = lambda w: {"lambda_q1": w["lq1"], "lambda_k1": w["lk1"],  # noqa: E731
                      "lambda_q2": w["lq2"], "lambda_k2": w["lk2"],
                      "subln": w["subln"]}
    tree = {"embed": {"embedding": canon["embed"]},
            "ln_f": {"scale": canon["lnf_g"], "bias": canon["lnf_b"]}}
    for l, w in enumerate(canon["layers"]):
        kind, pre = sambay_weights.layer_kind(shape, l), f"layer_{l}_"
        tree[pre + "ln1"] = {"scale": w["ln1_g"], "bias": w["ln1_b"]}
        tree[pre + "ln2"] = {"scale": w["ln2_g"], "bias": w["ln2_b"]}
        tree[pre + "mlp"] = {"gate_up": lin(w["w_gate_up"]),
                             "down": lin(w["w_down"])}
        if kind == sambay_weights.MAMBA:
            tree[pre + "mamba"] = {
                "in_proj": lin(w["w_in"]), "conv_kernel": w["conv_w"],
                "conv_bias": w["conv_b"], "x_proj": lin(w["w_x"]),
                "dt_proj": lin(w["w_dt"], w["dt_b"]),
                "A_log": w["A_log"].T, "D": w["D"],
                "out_proj": lin(w["w_out"])}
        elif kind in (sambay_weights.SWA, sambay_weights.FULL):
            tree[pre + "attn"] = {"qkv": lin(w["w_qkv"], w["b_qkv"]),
                                  "out": lin(w["w_o"], w["b_o"]),
                                  "diff": diff(w)}
        elif kind == sambay_weights.CROSS:
            tree[pre + "cross"] = {"q": lin(w["w_q"], w["b_q"]),
                                   "out": lin(w["w_o"], w["b_o"]),
                                   "diff": diff(w)}
        else:
            tree[pre + "gmu"] = {"in_proj": lin(w["w_in"]),
                                 "out_proj": lin(w["w_out"])}
    return tree


def config(shape: dict, positions: int, **overrides):
    from ray_lightning_tpu.models.sambay import SambaYConfig
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "sliding_window", "mb_per_layer",
            "layer_norm_eps", "tie_word_embeddings",
            "max_position_embeddings", "mamba_d_state", "mamba_d_conv",
            "mamba_expand", "mamba_dt_rank")
    kw = {k: shape[k] for k in keys if k in shape}
    return SambaYConfig(max_seq_len=positions, **{**kw, **overrides})


def build(shape: dict, workload: dict, key):
    """``(model, params, facts)``: the decode-mode program and its
    weights from the key; ``facts`` are the item sizes the accepted
    GPT-2 metrics expect beside the kind's own."""
    from ray_lightning_tpu.models.sambay import SambaYLM
    model = SambaYLM(config(shape, max_positions(shape, workload),
                            decode=True))
    params = program_tree(sambay_weights.make_canonical(key, shape), shape)
    return model, params, {"kv_itemsize": 2, "weight_itemsize": 2}


def make_reference(shape: dict, key, mode: str = "f32", **kw):
    """``f(tokens (T,), rows) -> (len(rows), V)`` teacher-forced float32
    logits from the benchmark's own weights; ``f.free()`` drops them."""
    params = sambay_weights.make_canonical(key, shape)
    fn = sambay_reference.make_logits_fn(shape, mode, **kw)
    return lambda tokens, rows: fn(params, tokens, rows)
