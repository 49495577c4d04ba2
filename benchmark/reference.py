"""The plain reference: GPT-2 as published, in ``jax.numpy`` and float32.

Nothing here imports the program under test or takes anything it made: the
weights come from ``weights.make_canonical`` (the benchmark's own, from the
seed), the layer equations are written out below. Float32 matmuls run at
``Precision.HIGHEST`` (on a TPU a float32 dot is otherwise bf16 passes).

Departures from the published model, both forced by the program, which
this benchmark may not edit: LayerNorm's epsilon is the configuration
file's ``layer_norm_epsilon`` (1e-6 as run, GPT-2 publishes 1e-5), and
dropout is 0.

``mode`` is the precision of every matmul (linear layers, the attention
products, the head); everything else stays float32:

- ``"f32"``  — the reference.
- ``"bf16"`` — operands rounded to bfloat16, float32 accumulation: what the
  configuration states (bf16 compute); a witness, not a control.
- ``"fp8"``  — the nearest precision below bf16, the **control** that has
  to come out as not correct: operands in float8_e4m3fn under a per-tensor
  absmax scale, and in a linear layer's backward the incoming gradient in
  float8_e5m2 (the recipe an fp8 training PR would bring).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "bf16", "fp8")


def _quantize(x, dtype):
    """``x`` rounded to an 8-bit float under a per-tensor absmax scale."""
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max) + 1e-30
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _round_operand(x, mode: str):
    """``x`` rounded to the mode's precision, with a straight-through
    gradient: the forward value is the rounded one, the cotangent passes
    in float32 (a cast's own transpose would round the cotangent to fp8
    without a scale and flush it to zero)."""
    if mode == "f32":
        return x
    if mode == "bf16":
        low = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "fp8":
        low = _quantize(x, jnp.float8_e4m3fn)
    else:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    return x + jax.lax.stop_gradient(low - x)


@jax.custom_vjp
def _mm_fp8(x, w):
    """The usual fp8 recipe for a linear layer: e4m3 operands forward, and
    backward the incoming gradient in e5m2 against the e4m3 operands."""
    return jnp.matmul(_quantize(x, jnp.float8_e4m3fn),
                      _quantize(w, jnp.float8_e4m3fn), precision=HIGHEST)


def _mm_fp8_fwd(x, w):
    return _mm_fp8(x, w), (x, w)


def _mm_fp8_bwd(res, dy):
    x, w = res
    dy = _quantize(dy, jnp.float8_e5m2)
    xq = _quantize(x, jnp.float8_e4m3fn)
    wq = _quantize(w, jnp.float8_e4m3fn)
    dx = jnp.matmul(dy, wq.T, precision=HIGHEST)
    dw = jnp.matmul(xq.reshape(-1, xq.shape[-1]).T,
                    dy.reshape(-1, dy.shape[-1]), precision=HIGHEST)
    return dx, dw


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(x, w, mode: str):
    """``x (..., k) @ w (k, n)`` in the mode's precision."""
    if mode == "fp8":
        return _mm_fp8(x, w)
    return jnp.matmul(_round_operand(x, mode), _round_operand(w, mode),
                      precision=HIGHEST)


def _layer_norm(x, g, b, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _block(x, lw, n_head: int, eps: float, mode: str):
    batch, seq, d = x.shape
    dh = d // n_head
    h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], eps)
    qkv = _mm(h, lw["w_qkv"], mode) + lw["b_qkv"]
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(batch, seq, n_head, dh)
               for i in range(3))
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round_operand(q, mode),
                        _round_operand(k, mode),
                        precision=HIGHEST) * dh ** -0.5
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", _round_operand(att, mode),
                     _round_operand(v, mode),
                     precision=HIGHEST).reshape(batch, seq, d)
    x = x + _mm(out, lw["w_o"], mode) + lw["b_o"]
    h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"], eps)
    h = jax.nn.gelu(_mm(h, lw["w_fc"], mode) + lw["b_fc"], approximate=True)
    return x + _mm(h, lw["w_proj"], mode) + lw["b_proj"]


def hidden(params: dict, tokens, n_head: int, eps: float, mode: str):
    """Final hidden states (after ln_f) of (B, T) tokens at positions
    0..T-1; one checkpointed block per scan step so a backward pass holds
    one layer's activations at a time."""
    seq = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:seq][None]
    stacked = {k: params[k] for k in weights.STACKED}

    @jax.checkpoint
    def body(x, lw):
        return _block(x, lw, n_head, eps, mode), None

    x, _ = jax.lax.scan(body, x, stacked)
    return _layer_norm(x, params["lnf_g"], params["lnf_b"], eps)


def logits(params: dict, tokens, n_head: int, eps: float, mode: str):
    """(B, T, V) float32 next-token logits through the tied head."""
    return _mm(hidden(params, tokens, n_head, eps, mode),
               params["wte"].T, mode)


def lm_loss(params: dict, inputs, targets, n_head: int, eps: float,
            mode: str):
    """Mean next-token cross entropy over every position."""
    lg = logits(params, inputs, n_head, eps, mode)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def _tree(fn, *trees):
    return {k: fn(*(t[k] for t in trees)) for k in trees[0]}


def make_train_step(shape: dict, hp: dict, row_block: int, mode: str = "f32",
                    rows=None,
                    param_shardings=None, row_sharding=None):
    """``step(params, m, v, t, inputs, targets) -> (params, m, v, loss,
    grad_norms)``: gradients of the mean loss over the whole batch,
    accumulated ``row_block`` rows at a time so it fits beside the Adam
    state, then one AdamW update (optax's: decay on every leaf, decoupled,
    scaled by the learning rate; bias-corrected moments; eps outside the
    square root). ``rows`` keeps only these rows of the batch and takes the
    mean over them — the planted faults (half the batch left out; one
    chip's share with the exchange left out), never the reference."""
    n_head, eps = shape["n_head"], float(shape["layer_norm_epsilon"])
    lr, b1, b2 = hp["lr"], hp["b1"], hp["b2"]
    adam_eps, wd = hp["eps"], hp["weight_decay"]
    grad = jax.value_and_grad(functools.partial(
        lm_loss, n_head=n_head, eps=eps, mode=mode))

    def step(params, m, v, t, inputs, targets):
        if rows is not None:
            inputs, targets = inputs[rows[0]:rows[1]], targets[rows[0]:rows[1]]
        n_blocks = inputs.shape[0] // row_block
        xb = inputs.reshape(n_blocks, row_block, -1)
        yb = targets.reshape(n_blocks, row_block, -1)

        def body(acc, xy):
            x, y = xy
            if row_sharding is not None:
                x = jax.lax.with_sharding_constraint(x, row_sharding)
                y = jax.lax.with_sharding_constraint(y, row_sharding)
            loss, g = grad(params, x, y)
            return _tree(jnp.add, acc, g), loss

        g, losses = jax.lax.scan(body, _tree(jnp.zeros_like, params),
                                 (xb, yb))
        g = _tree(lambda a: a / n_blocks, g)
        t = t + 1
        m = _tree(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = _tree(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = _tree(
            lambda p, m_, v_: p - lr * ((m_ / c1)
                                        / (jnp.sqrt(v_ / c2) + adam_eps)
                                        + wd * p), params, m, v)
        return params, m, v, jnp.mean(losses), weights.leaf_norms(g)

    kw = {}
    if param_shardings is not None:
        kw = dict(out_shardings=(param_shardings, param_shardings,
                                 param_shardings, None, None))
    return jax.jit(step, donate_argnums=(0, 1, 2), **kw)


def make_change_norms(shape: dict):
    """``f(params, key) -> per-leaf ||params - init||``: the initial weights
    are made again from the key inside the program, so no second copy of
    them is ever held."""
    def change(params, key):
        init = weights.make_canonical(key, shape)
        return weights.leaf_norms(_tree(jnp.subtract, params, init))
    return jax.jit(change)


def make_logits_fn(shape: dict, mode: str):
    """``f(params, tokens (1, T)) -> (T, V)`` teacher-forced logits."""
    n_head, eps = shape["n_head"], float(shape["layer_norm_epsilon"])
    return jax.jit(lambda params, tokens: logits(
        params, tokens, n_head, eps, mode)[0])
