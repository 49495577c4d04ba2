"""Traffic kind ``train_fit``: one ``Trainer.fit`` of a GPT-2 LM on
synthetic tokens, the window taken inside it by a callback.

Set-up builds one trainer, drives it from the seed through its first
``reference.steps`` optimizer steps (their losses, the first gradient's
per-leaf norms read back from Adam's first moment, and the per-leaf norm of
the parameters' change are kept for the check), warms a few more, and hands
that same compiled step and state to the window: the window opens and
closes on a ``block_until_ready`` of the train state, and in between the
host stays at most ``run_ahead`` steps ahead of the device by waiting for
the loss of an older step — never for the newest, so the device is never
left without work.

Workload file keys: ``strategy`` (class name in ``ray_lightning_tpu`` and
``num_workers``), ``batch``, ``seq_len``, ``lr``, ``weight_decay``,
``adam`` (b1, b2, eps as the program's GPTModule configures them),
``remat_policy``, ``warmup_steps``, ``run_ahead``, ``data_pool_batches``,
``trace`` (start_s, seconds, synced_steps), ``reference`` (steps,
row_block), ``limits``.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

from benchmark import reference, weights
from benchmark.harness import note


class TokenFeed:
    """(inputs, targets) batches of uniform random token ids from the
    seed: a pool of distinct batches, cycled — every row differs, every
    step costs the same."""

    def __init__(self, seed: int, batch: int, seq_len: int, vocab: int,
                 pool: int):
        rng = np.random.default_rng([int(seed), 1])
        toks = rng.integers(0, vocab, size=(pool, batch, seq_len + 1),
                            dtype=np.int32)
        self.batches = [(t[:, :-1], t[:, 1:]) for t in toks]

    def __iter__(self):
        i = 0
        while True:
            yield self.batches[i % len(self.batches)]
            i += 1


def adam_mu(opt_state):
    """The first-moment tree inside an optax state, wherever it sits."""
    import jax
    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def leaf_gaps(prog: dict, ref: dict, skip: dict = None):
    """Per leaf (one per layer for block leaves): |prog norm - ref norm|
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. ``skip[name]`` masks leaves out. Returns the gaps
    of the leaves kept and their labels."""
    names = sorted(ref)
    p = np.concatenate([np.ravel(prog[n]) for n in names]).astype(np.float64)
    r = np.concatenate([np.ravel(ref[n]) for n in names]).astype(np.float64)
    keep = np.ones_like(r, bool) if skip is None else ~np.concatenate(
        [np.ravel(skip[n]) for n in names])
    gaps = np.abs(p - r) / np.maximum(r, np.median(r[keep]))
    labels = np.array([f"{n}[{i}]" if np.ndim(ref[n]) else n
                       for n in names for i in range(np.size(ref[n]))])
    return gaps[keep], labels[keep]


def global_gap(prog: dict, ref: dict) -> float:
    """The gap of the norm over all leaves together."""
    def total(t):
        return float(np.sqrt(sum(np.sum(np.square(
            np.asarray(v, np.float64))) for v in t.values())))
    return abs(total(prog) - total(ref)) / total(ref)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.models import GPTModule
    from ray_lightning_tpu.models.transformer import TransformerConfig

    w, shape = ctx.workload, ctx.shape
    batch, seq_len = int(w["batch"]), int(w["seq_len"])
    n_head, b1 = shape["n_head"], float(w["adam"]["b1"])
    ref_steps = int(w["reference"]["steps"])
    warmup, run_ahead = int(w["warmup_steps"]), int(w["run_ahead"])
    feed = TokenFeed(ctx.seed, batch, seq_len, shape["vocab_size"],
                     int(w["data_pool_batches"]))

    cfg = TransformerConfig(
        vocab_size=shape["vocab_size"], max_seq_len=shape["n_positions"],
        d_model=shape["n_embd"], n_heads=n_head, n_layers=shape["n_layer"],
        d_ff=4 * shape["n_embd"], dtype=jnp.bfloat16,
        param_dtype=jnp.float32, causal=True, scan_layers=True, remat=True,
        remat_policy=w["remat_policy"])

    class BenchModule(GPTModule):
        """The program's GPT module, given the benchmark's weights and
        tokens (what a user's own module would bring)."""

        def init_variables(self, model, rng, batch):
            key = weights.key_from_tokens(batch[0])
            return {"params": weights.program_tree(
                weights.make_canonical(key, shape), n_head, scanned=True)}

        def train_dataloader(self):
            return feed

        def val_dataloader(self):
            return None

    module = BenchModule(
        config=cfg, batch_size=batch, seq_len=seq_len, lr=float(w["lr"]),
        weight_decay=float(w["weight_decay"]), optimizer="adamw")

    grad_norms = jax.jit(lambda mu: {
        k: v / (1.0 - b1) for k, v in weights.canonical_norms(mu).items()})
    change_norms = jax.jit(lambda p, tokens: weights.canonical_norms(
        jax.tree_util.tree_map(jnp.subtract, p, weights.program_tree(
            weights.make_canonical(weights.key_from_tokens(tokens), shape),
            n_head, scanned=True))))

    sl = ctx.slice
    tr = w["trace"]

    class Window(rlt.Callback):
        def __init__(self):
            self.losses, self.grad, self.change = [], None, None
            self.t_open = self.t_close = None
            self.k_open = self.k_close = 0
            self.pending = collections.deque()
            self.slice_steps = 0
            self.synced_left = int(tr["synced_steps"]) if sl else 0
            self.step_ms, self.t_prev = [], None
            self.last_loss = None
            self._span = None

        def _enter(self, name=None):
            """Close the open harness span and, while tracing, open the
            next one."""
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None
            if name is not None and sl is not None and sl.running:
                self._span = jax.profiler.TraceAnnotation("harness." + name)
                self._span.__enter__()

        def on_train_batch_start(self, trainer, module, batch, batch_idx):
            self._enter("step_dispatch")

        def on_train_batch_end(self, trainer, module, logs, batch, batch_idx):
            k = trainer.global_step
            if self.t_open is None:
                self._set_up(trainer, logs, k)
                return
            self._enter("lag_sync")
            now = self._pace(trainer, logs, k)
            if sl is not None and not sl.done and not sl.running \
                    and now - self.t_open >= float(tr["start_s"]):
                jax.block_until_ready(trainer.train_state)
                sl.start()
                self.k_slice = k
            elif sl is not None and sl.running \
                    and now - sl.t0 >= float(tr["seconds"]):
                jax.block_until_ready(trainer.train_state)
                self._enter()
                sl.stop()
                self.slice_steps = k - self.k_slice
                self.t_prev = time.perf_counter()
            elif now - self.t_open >= ctx.seconds and (
                    sl is None or (sl.done and self.synced_left == 0)):
                jax.block_until_ready(trainer.train_state)
                self.t_close, self.k_close = time.perf_counter(), k
                trainer.should_stop = True
            self._enter("trainer_loop")

        def _set_up(self, trainer, logs, k):
            if k <= ref_steps:
                self.losses.append(logs["loss"])
            if k == 1:
                self.grad = grad_norms(adam_mu(trainer.train_state.opt_state))
            if k == ref_steps:
                self.change = change_norms(trainer.train_state.params,
                                           feed.batches[0][0])
            if k >= max(warmup, ref_steps):
                jax.block_until_ready(trainer.train_state)
                self.losses = [float(x) for x in self.losses]
                self.grad = jax.device_get(self.grad)
                self.change = jax.device_get(self.change)
                self.t_open, self.k_open = ctx.window_open(), k

        def _pace(self, trainer, logs, k):
            """Stay ``run_ahead`` steps ahead of the device; after the
            traced slice, a few steps are waited for one by one and timed
            (``train.step_ms_p50``)."""
            if sl is not None and sl.done and self.synced_left > 0:
                float(logs["loss"])
                now = time.perf_counter()
                self.step_ms.append((now - self.t_prev) * 1e3)
                self.t_prev = now
                self.synced_left -= 1
                return now
            self.pending.append(logs["loss"])
            while len(self.pending) > run_ahead:
                self.last_loss = float(self.pending.popleft())
            return time.perf_counter()

    strategy = getattr(rlt, w["strategy"]["class"])(
        num_workers=int(w["strategy"]["num_workers"]),
        use_tpu=not ctx.rehearse)
    win = Window()
    trainer = rlt.Trainer(strategy=strategy, max_epochs=1, max_steps=-1,
                          callbacks=[win], limit_val_batches=0,
                          enable_checkpointing=False,
                          enable_progress_bar=False, seed=0)
    trainer.fit(module)
    win._enter()
    if win.t_close is None:
        raise RuntimeError("the fit ended before the window closed")

    steps = win.k_close - win.k_open
    wall = win.t_close - win.t_open
    tokens = steps * batch * seq_len
    note(phase="window", steps=steps, wall_s=round(wall, 4), tokens=tokens,
         first_losses=win.losses, last_loss=win.last_loss,
         generator_lateness_s=0.0)
    facts = {"kind": "train_fit", "batch": batch, "seq_len": seq_len,
             "steps": steps, "wall_s": wall, "step_ms": win.step_ms,
             "slice_steps": win.slice_steps,
             "slice_s": (sl.t1 - sl.t0) if sl is not None else None}
    prog = {"losses": win.losses, "grad": win.grad, "change": win.change}

    def check() -> dict:
        nonlocal trainer, module, win
        trainer.train_state = None
        trainer = module = win = None
        gc.collect()
        jax.clear_caches()
        ref = run_reference(ctx, feed)
        return judge(prog, ref, w["limits"], ref_steps)

    return {"attempted": steps, "failed": 0,
            "end_to_end": {"train_tokens_per_s_per_chip":
                           tokens / wall / ctx.chips},
            "facts": facts, "check": check, "program": prog}


def run_reference(ctx, feed, mode: str = "f32", rows=None) -> dict:
    """The plain reference over the first ``reference.steps`` batches of
    the same feed, its weights from the same key: losses, first-gradient norms and
    parameter-change norms per leaf. On several chips its state is cut
    over them along each leaf's widest axis and the rows of a block are
    spread over them (it needs the memory, not the speed)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    w, shape = ctx.workload, ctx.shape
    hp = dict(w["adam"], lr=float(w["lr"]),
              weight_decay=float(w["weight_decay"]))
    row_block = int(w["reference"]["row_block"])
    if rows is not None and (rows[1] - rows[0]) % row_block:
        row_block = rows[1] - rows[0]     # a planted fault's odd share
    shardings = row_sharding = None
    if ctx.chips > 1:
        mesh = Mesh(np.array(ctx.devices[:ctx.chips]), ("x",))

        def cut(name, shp):
            first = 1 if name in weights.STACKED else 0
            axes = [a for a in range(first, len(shp))
                    if shp[a] % ctx.chips == 0]
            spec = [None] * len(shp)
            if axes:
                spec[max(axes, key=lambda a: shp[a])] = "x"
            return NamedSharding(mesh, P(*spec))

        shardings = {n: cut(n, s)
                     for n, s in weights.canonical_shapes(shape).items()}
        if row_block % ctx.chips == 0:
            row_sharding = NamedSharding(mesh, P("x", None))
    key = weights.key_from_tokens(feed.batches[0][0])
    make = jax.jit(lambda k: weights.make_canonical(k, shape),
                   out_shardings=shardings)
    params = make(key)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p),
                    out_shardings=shardings)
    m, v = zeros(params), zeros(params)
    step = reference.make_train_step(
        shape, hp, row_block, mode=mode, rows=rows,
        param_shardings=shardings, row_sharding=row_sharding)
    losses, grad, t = [], None, jnp.zeros((), jnp.float32)
    batches = iter(feed)
    for i in range(int(w["reference"]["steps"])):
        x, y = next(batches)
        params, m, v, loss, norms = step(params, m, v, t + i, x, y)
        losses.append(float(loss))
        if i == 0:
            grad = jax.device_get(norms)
    change = jax.device_get(
        reference.make_change_norms(shape)(params, key))
    del params, m, v
    return {"losses": losses, "grad": grad, "change": change}


def judge(prog: dict, ref: dict, limits: dict, ref_steps: int) -> dict:
    """The numbers compared, each beside its limit. The steps' losses are
    printed on an earlier line and not compared: neither the control nor a
    planted fault reads three times what sound runs do (PERF.md)."""
    out = {}
    grad, grad_leaf = leaf_gaps(prog["grad"], ref["grad"])
    out["grad_norm_gap"] = (float(grad.max()), limits["grad_norm_gap"])
    # leaves whose gradient is nought to rounding in the reference (a key's
    # bias under softmax) move under Adam by round-off alone: left out by a
    # rule on the reference's gradient, not by name
    flat = np.concatenate([np.ravel(v) for v in ref["grad"].values()])
    floor = 1e-3 * float(np.median(flat))
    skip = {n: np.asarray(v) < floor for n, v in ref["grad"].items()}
    change, change_leaf = leaf_gaps(prog["change"], ref["change"], skip)
    out["param_change_gap"] = (float(change.max()),
                               limits["param_change_gap"])

    def spread(g):
        return [float(np.percentile(g, q)) for q in (50, 90, 100)]

    note(phase="judge", ref_losses=ref["losses"], prog_losses=prog["losses"],
         loss_gaps=[abs(a - b) for a, b in zip(
             prog["losses"][:ref_steps], ref["losses"])],
         grad_worst_leaf=str(grad_leaf[grad.argmax()]),
         change_worst_leaf=str(change_leaf[change.argmax()]),
         grad_gap_p50_p90_max=spread(grad),
         change_gap_p50_p90_max=spread(change),
         grad_global_gap=global_gap(prog["grad"], ref["grad"]),
         change_global_gap=global_gap(prog["change"], ref["change"]),
         leaves_skipped=int(sum(int(np.sum(s)) for s in skip.values())))
    return out
