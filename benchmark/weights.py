"""GPT-2 weights from ``--seed``, made on the device in one jitted call.

The canonical layout (what the plain reference reads) stacks every block
leaf on a leading layer axis::

    wte (V,d)  wpe (P,d)  lnf_g lnf_b (d,)
    ln1_g ln1_b ln2_g ln2_b b_o b_proj (L,d)
    w_qkv (L,d,3d)  b_qkv (L,3d)  w_o (L,d,d)
    w_fc (L,d,4d)   b_fc (L,4d)   w_proj (L,4d,d)

Init is GPT-2's: normal(0, initializer_range) for embeddings and matrices,
residual projections (w_o, w_proj) scaled by 1/sqrt(2L), biases 0,
LayerNorm gain 1. ``program_tree`` renames and reshapes the same numbers
into the tree ``ray_lightning_tpu.models.transformer.TransformerLM`` binds
(the one place that knows the program's parameter paths);
``canonical_norms`` maps a program tree's per-leaf norms back.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

STACKED = ("ln1_g", "ln1_b", "w_qkv", "b_qkv", "w_o", "b_o",
           "ln2_g", "ln2_b", "w_fc", "b_fc", "w_proj", "b_proj")
FLAT = ("wte", "wpe", "lnf_g", "lnf_b")


def seed_key(seed: int):
    """A threefry key from any whole number (seeds run past 2**31)."""
    seed = int(seed)
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data))


def key_from_tokens(tokens):
    """Traceable: a key from the first two token ids of a batch. A train
    cell's weights hang on the seed through its token feed, so that the
    key reaches the program's jitted init as data (an argument), never as
    a constant baked into the program: a constant would change the
    program with every seed and miss the compile cache."""
    return jax.random.wrap_key_data(
        jnp.ravel(tokens)[:2].astype(jnp.uint32))


def canonical_shapes(shape: dict) -> dict:
    d, n_layer = shape["n_embd"], shape["n_layer"]
    return {
        "wte": (shape["vocab_size"], d), "wpe": (shape["n_positions"], d),
        "lnf_g": (d,), "lnf_b": (d,),
        "ln1_g": (n_layer, d), "ln1_b": (n_layer, d),
        "ln2_g": (n_layer, d), "ln2_b": (n_layer, d),
        "w_qkv": (n_layer, d, 3 * d), "b_qkv": (n_layer, 3 * d),
        "w_o": (n_layer, d, d), "b_o": (n_layer, d),
        "w_fc": (n_layer, d, 4 * d), "b_fc": (n_layer, 4 * d),
        "w_proj": (n_layer, 4 * d, d), "b_proj": (n_layer, d),
    }


def make_canonical(key, shape: dict) -> dict:
    """Traceable: the whole weight set in float32 from one key."""
    std = float(shape.get("initializer_range", 0.02))
    resid = std / math.sqrt(2 * shape["n_layer"])
    out = {}
    for i, (name, shp) in enumerate(sorted(canonical_shapes(shape).items())):
        if name.startswith("ln") and name.endswith("_g"):
            out[name] = jnp.ones(shp, jnp.float32)
        elif name.startswith(("b_", "ln")):
            out[name] = jnp.zeros(shp, jnp.float32)
        else:
            scale = resid if name in ("w_o", "w_proj") else std
            out[name] = scale * jax.random.normal(
                jax.random.fold_in(key, i), shp, jnp.float32)
    return out


def program_tree(canon: dict, n_head: int, scanned: bool) -> dict:
    """The canonical weights under TransformerLM's parameter paths:
    scanned = the stacked ``stack/layers/block`` layout training uses,
    else one ``stack/block_i`` per layer (the serving layout)."""
    n_layer, d = canon["ln1_g"].shape
    dh = d // n_head

    def block(pick):
        return {
            "ln1": {"scale": pick("ln1_g"), "bias": pick("ln1_b")},
            "ln2": {"scale": pick("ln2_g"), "bias": pick("ln2_b")},
            "attn": {
                "qkv": {"kernel": pick("w_qkv", (d, 3, n_head, dh)),
                        "bias": pick("b_qkv", (3, n_head, dh))},
                "out": {"kernel": pick("w_o"), "bias": pick("b_o")}},
            "mlp": {"up": {"kernel": pick("w_fc"), "bias": pick("b_fc")},
                    "down": {"kernel": pick("w_proj"),
                             "bias": pick("b_proj")}},
        }

    if scanned:
        stack = {"layers": {"block": block(
            lambda k, s=None: canon[k] if s is None
            else canon[k].reshape((n_layer,) + s))}}
    else:
        stack = {f"block_{i}": block(
            lambda k, s=None, i=i: canon[k][i] if s is None
            else canon[k][i].reshape(s)) for i in range(n_layer)}
    return {"wte": {"embedding": canon["wte"]},
            "wpe": {"embedding": canon["wpe"]},
            "stack": stack,
            "ln_f": {"scale": canon["lnf_g"], "bias": canon["lnf_b"]}}


_PROGRAM_PATHS = {
    ("ln1", "scale"): "ln1_g", ("ln1", "bias"): "ln1_b",
    ("ln2", "scale"): "ln2_g", ("ln2", "bias"): "ln2_b",
    ("attn", "qkv", "kernel"): "w_qkv", ("attn", "qkv", "bias"): "b_qkv",
    ("attn", "out", "kernel"): "w_o", ("attn", "out", "bias"): "b_o",
    ("mlp", "up", "kernel"): "w_fc", ("mlp", "up", "bias"): "b_fc",
    ("mlp", "down", "kernel"): "w_proj", ("mlp", "down", "bias"): "b_proj",
}


#: the fused qkv leaves are judged as three: a key's bias has no gradient
#: under softmax, the query's and the value's do
_SPLIT = {"w_qkv": ("w_q", "w_k", "w_v"), "b_qkv": ("b_q", "b_k", "b_v")}


def canonical_norms(tree: dict) -> dict:
    """Traceable: L2 norm of every leaf of a *scanned* program tree (params,
    a gradient, an Adam moment) under its canonical name — one number per
    layer for block leaves, (L,), and a scalar for the rest. A reshape
    keeps the norm, so these compare with ``leaf_norms`` of the reference's
    canonical tree."""
    def norm(x, keep_first):
        x = x.astype(jnp.float32)
        axes = tuple(range(1 if keep_first else 0, x.ndim))
        return jnp.sqrt(jnp.sum(x * x, axis=axes))

    block = tree["stack"]["layers"]["block"]
    out = {"wte": norm(tree["wte"]["embedding"], False),
           "wpe": norm(tree["wpe"]["embedding"], False),
           "lnf_g": norm(tree["ln_f"]["scale"], False),
           "lnf_b": norm(tree["ln_f"]["bias"], False)}
    for path, name in _PROGRAM_PATHS.items():
        leaf = block
        for p in path:
            leaf = leaf[p]
        if name in _SPLIT:      # (L, [d,] 3, H, Dh): q, k, v apart
            for i, part in enumerate(_SPLIT[name]):
                out[part] = norm(jnp.take(leaf, i, axis=leaf.ndim - 3), True)
        else:
            out[name] = norm(leaf, True)
    return out


def leaf_norms(canon: dict) -> dict:
    """Traceable: the same norms of a canonical tree."""
    out = {}

    def norm(x, keep_first):
        axes = tuple(range(1 if keep_first else 0, x.ndim))
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                axis=axes))

    for name, x in canon.items():
        if name in _SPLIT:      # (L, [d,] 3d): q | k | v along the last axis
            for part, piece in zip(_SPLIT[name], jnp.split(x, 3, axis=-1)):
                out[part] = norm(piece, True)
        else:
            out[name] = norm(x, name in STACKED)
    return out
