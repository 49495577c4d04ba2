#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run with no arguments on a machine with one TPU chip, it drives the main
path once through the entry points a user calls, at GPT-2-small's
published width and depth, in ONE process (a chip belongs to one process):

1. ``train``  — ``Trainer(strategy=RayStrategy(num_workers=1, use_tpu=True))
   .fit(GPTModule(gpt2_config("small")))``: bf16, adamw,
   ``remat_policy="dots_with_no_batch_dims"``, batch 8 x 512 synthetic
   tokens from a fixed seed. Loss finite at every step, lower at the end.
2. ``serve``  — those params (through ``unstack_scan_params``) in a
   ``decode=True, scan_layers=False`` model behind ``ServeClient``: 8 ragged
   requests, half greedy, half sampled; every request retires and the
   greedy rows equal ``models.generate.generate()`` token for token.
3. ``kernels`` — the same requests through the paged engine with int8 KV
   under ``attention_kernel`` xla vs pallas, and through int8 weights under
   ``matmul_kernel`` xla vs pallas: token agreement, first differing
   position, and the max abs logit difference of a teacher-forced probe
   (int4 weights: the probe only, no engine);
   plus train steps with ``attention_impl="flash"`` and with the default
   seat (which takes the same kernel by itself on a TPU) against the dense
   path.
   Every Pallas path must hold its kernel (``tpu_custom_call``) in the
   program it ran — never an interpreted expansion.

``--chips 4`` runs ONLY the four-chip path and what it is compared with:
GPT-2-small for 3 steps under ``FSDPStrategy(num_workers=4)``, then the
same seed and global batch on one device; per-step losses agree within
``LOSS_TOL`` and parameters + optimizer state sit on four distinct devices
at about a quarter each.

One JSON line per phase; the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Any failed phase makes the exit code non-zero and ``"ok": false``. Without
a TPU the script fails at once, before any phase. ``--rehearse`` walks the
same control flow at a tiny size on the CPU (Pallas in interpret mode) and
refuses to run on a TPU, so no rehearsal ever prints ``"ok": true`` beside
``"platform": "tpu"``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback

#: per-step |loss(4 chips) - loss(1 chip)| bound: same seed, same global
#: batch, bf16 compute — only collective/reduction order differs
LOSS_TOL = 0.01
#: flash vs dot first-step loss: same init and batch, bf16 attention with
#: an online (flash) vs a materialized (dot) softmax
FLASH_TOL = 0.01


@dataclasses.dataclass(frozen=True)
class Sizes:
    size: str = "small"          # 12 layers, d_model 768, 12 heads, d_ff 3072
    vocab: int = 50257
    max_seq_len: int = 1024
    batch: int = 8
    seq_len: int = 512
    train_steps: int = 30
    multichip_steps: int = 3
    num_slots: int = 8
    prefill_len: int = 64
    prompt_min: int = 8
    max_new: int = 32
    page_size: int = 16
    probe_len: int = 24          # teacher-forced probe positions per row
    int4_group: int = 64         # quant.DEFAULT_GROUP_SIZE (divides head_dim)


#: the rehearsal walks the same code at a size a CPU finishes in minutes
TINY = Sizes(size="nano", vocab=256, max_seq_len=64, batch=8, seq_len=32,
             train_steps=6, multichip_steps=3, num_slots=8, prefill_len=8,
             prompt_min=2, max_new=6, page_size=4, probe_len=6,
             int4_group=8)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileMeter:
    """Sums jax's own backend-compile events and persistent-cache hits,
    so a phase can split its wall clock into compile and the rest."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name: str, secs: float, **_kw) -> None:
        # the backend (XLA / Mosaic) compile only: tracing and lowering
        # events nest inside one another and would be counted twice, so
        # they stay on the "run" side of the split
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return self.compile_s, self.cache_hits


def check(ok, message) -> None:
    """The smoke's assertion: raises under ``python -O`` too."""
    if not ok:
        raise AssertionError(message)


class Run:
    """One smoke run: the device, the compile meter and the names of the
    phases that failed."""

    def __init__(self, device, meter: CompileMeter):
        self.device, self.meter = device, meter
        self.on_tpu = device.platform == "tpu"
        self.failed: list = []

    def phase(self, name: str) -> "Phase":
        return Phase(name, self)

    def skip(self, name: str, why: str) -> None:
        self.failed.append(name)
        emit(phase=name, ok=False, error=f"skipped: {why}")


class Phase:
    """Times one phase and prints its JSON line; a raised phase prints
    ``"ok": false`` with the error and is counted as failed."""

    def __init__(self, name: str, run: Run):
        self.name, self.run = name, run
        self.meter, self.device = run.meter, run.device
        self.fields: dict = {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.meter.snapshot()
        return self.fields

    def __exit__(self, exc_type, exc, tb):
        if exc is not None and not isinstance(exc, Exception):
            return False   # an interrupt or exit is not a failed phase
        wall = time.perf_counter() - self.t0
        c1, h1 = self.meter.snapshot()
        stats = self.device.memory_stats()   # None on the CPU backend
        line = dict(
            phase=self.name, ok=exc is None,
            seconds=round(wall, 3), compile_s=round(c1 - self.c0, 3),
            run_s=round(wall - (c1 - self.c0), 3),
            cache_hits=h1 - self.h0,
            peak_bytes_in_use=(stats or {}).get("peak_bytes_in_use"),
            platform=self.device.platform, **self.fields)
        if exc is not None:
            line["error"] = f"{exc_type.__name__}: {exc}"[:2000]
            traceback.print_exception(exc_type, exc, tb, file=sys.stderr)
            self.run.failed.append(self.name)
        emit(**line)
        return True   # the failure is recorded; the run ends non-zero


# --------------------------------------------------------------------- #
# shared builders
# --------------------------------------------------------------------- #
def train_config(sz: Sizes, **overrides):
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt2_config
    return gpt2_config(sz.size, vocab_size=sz.vocab,
                       max_seq_len=sz.max_seq_len, dtype=jnp.bfloat16,
                       scan_layers=True, remat=True,
                       remat_policy="dots_with_no_batch_dims", **overrides)


def fit(sz: Sizes, cfg, strategy, steps: int):
    """One ``Trainer.fit`` of ``steps`` optimizer steps; returns
    ``(trainer, module, losses, stamps)`` — every step's loss as a host
    float and the wall clock at which it was read."""
    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.models import GPTModule
    losses, stamps = [], []

    class Recorder(rlt.Callback):
        def on_train_batch_end(self, trainer, pl_module, outputs, batch,
                               batch_idx):
            losses.append(float(outputs["loss"]))
            stamps.append(time.perf_counter())

    module = GPTModule(config=cfg, batch_size=sz.batch, seq_len=sz.seq_len,
                       num_samples=sz.batch * sz.train_steps, lr=3e-4,
                       optimizer="adamw")   # one dataset for every fit
    trainer = rlt.Trainer(strategy=strategy, max_epochs=1, max_steps=steps,
                          callbacks=[Recorder()], limit_val_batches=0,
                          enable_checkpointing=False,
                          enable_progress_bar=False, seed=0)
    trainer.fit(module)
    return trainer, module, losses, stamps


def check_losses(losses, steps: int) -> None:
    import math
    check(len(losses) == steps, (len(losses), steps))
    check(all(math.isfinite(x) for x in losses), losses)
    check(losses[-1] < losses[0], (losses[0], losses[-1]))


def train_step_text(trainer, module) -> str:
    """Lowered text of the train step the trainer ran (same jitted
    function, same state, a batch of the same shape)."""
    batch = next(iter(module.train_dataloader()))
    return trainer._train_step.lower(trainer.train_state, batch).as_text()


def make_requests(sz: Sizes):
    """8 ragged in-distribution prompts; even rows greedy, odd sampled."""
    import numpy as np

    from ray_lightning_tpu.data.synthetic import synthetic_tokens
    rng = np.random.default_rng(0)
    stream = synthetic_tokens(sz.num_slots, sz.prefill_len, sz.vocab, seed=7)
    reqs = []
    for i in range(sz.num_slots):
        plen = int(rng.integers(sz.prompt_min, sz.prefill_len + 1))
        greedy = i % 2 == 0
        reqs.append(dict(prompt=[int(t) for t in stream[i, :plen]],
                         max_new_tokens=sz.max_new,
                         temperature=0.0 if greedy else 0.8,
                         top_k=None if greedy else 20))
    return reqs


def serve(model, params, reqs, sz: Sizes, **engine_kw):
    """The 8 requests through one ``ServeClient``; returns the token lists
    in request order, the decode step's lowered text and the tick count."""
    from ray_lightning_tpu.serve import ServeClient
    client = ServeClient(model, params, num_slots=sz.num_slots,
                         prefill_len=sz.prefill_len, **engine_kw)
    ids = [client.submit(**kw) for kw in reqs]
    out = client.run_until_idle()
    text = client.engine.lowered_step_text()
    client.shutdown()
    check(sorted(out) == sorted(ids), "a request never retired")
    for rid in ids:
        check(out[rid].finish_reason == "length", out[rid].finish_reason)
        check(len(out[rid].tokens) == sz.max_new, out[rid].tokens)
    return [out[rid].tokens for rid in ids], text, client.ops


def compare_tokens(a, b, greedy_rows):
    """Greedy-row agreement of two engines: ``(agree, first_diff)`` with
    ``first_diff = [row, position]`` of the earliest differing token."""
    for pos in range(len(a[0])):
        for r in greedy_rows:
            if a[r][pos] != b[r][pos]:
                return False, [r, pos]
    return True, None


def probe_logits(model, params, sz: Sizes, kv_dtype):
    """Teacher-forced page-native decode of ``probe_len`` positions over
    8 rows: every step's last-token logits, (steps, B, V) f32, and whether
    the probe's program holds a compiled Pallas kernel. The same tokens
    feed every model, so two kernels are compared on equal inputs (the
    serve engines diverge after their first differing token)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.data.synthetic import synthetic_tokens
    from ray_lightning_tpu.models.generate import decode_step_paged
    from ray_lightning_tpu.serve.pages import PagePool
    B = sz.num_slots
    pool = PagePool(model, B, sz.page_size, kv_dtype=kv_dtype)
    pp = pool.page_table.shape[1]
    need = -(-sz.probe_len // sz.page_size)
    table = np.full((B, pp), -1, np.int32)
    for r in range(B):
        table[r, :need] = np.arange(r * need, (r + 1) * need)
    toks = synthetic_tokens(B, sz.probe_len, sz.vocab, seed=11)
    step = jax.jit(lambda p, arena, t, pos: decode_step_paged(
        model, p, arena, t, pos, jnp.asarray(table)))
    arena, out = pool.arena, []
    holds = "tpu_custom_call" in step.lower(
        params, arena, jnp.asarray(toks[:, :1]),
        jnp.zeros((B, 1), jnp.int32)).as_text()
    for t in range(sz.probe_len):
        pos = jnp.full((B, 1), t, jnp.int32)
        logits, arena = step(params, arena, jnp.asarray(toks[:, t:t + 1]),
                             pos)
        out.append(np.asarray(logits, np.float32))
    return np.stack(out), holds


def probe_pair(models, params, sz: Sizes, kv_dtype, need_kernel: bool,
               prefix: str = "probe") -> dict:
    """xla-vs-pallas teacher-forced probe of one kernel: max abs logit
    difference, the logit scale it sits against, and top-1 agreement."""
    import numpy as np
    ref, _ = probe_logits(models[0], params, sz, kv_dtype)
    out, holds = probe_logits(models[1], params, sz, kv_dtype)
    if need_kernel:
        check(holds, f"{prefix}: the pallas program holds no kernel")
    return {
        f"{prefix}_max_abs_logit_diff": float(np.max(np.abs(ref - out))),
        f"{prefix}_logit_abs_max": float(np.max(np.abs(ref))),
        f"{prefix}_argmax_agreement": float(
            np.mean(ref.argmax(-1) == out.argmax(-1))),
        f"{prefix}_kernel_in_program": holds}


# --------------------------------------------------------------------- #
# the one-chip phases
# --------------------------------------------------------------------- #
def one_chip(sz: Sizes, run: Run) -> None:
    import jax
    import numpy as np

    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.models import TransformerLM
    from ray_lightning_tpu.models.generate import generate
    from ray_lightning_tpu.models.quant import quantize_params
    from ray_lightning_tpu.models.transformer import unstack_scan_params

    on_tpu = run.on_tpu

    def strategy():
        return rlt.RayStrategy(num_workers=1, use_tpu=on_tpu)

    state = {}
    with run.phase("train") as f:
        cfg = train_config(sz)
        trainer, module, losses, stamps = fit(sz, cfg, strategy(),
                                              sz.train_steps)
        check_losses(losses, sz.train_steps)
        step_s = float(np.median(np.diff(stamps)))
        f.update(model=f"gpt2-{sz.size}", n_layers=cfg.n_layers,
                 d_model=cfg.d_model, n_heads=cfg.n_heads, d_ff=cfg.d_ff,
                 vocab=cfg.vocab_size, batch=sz.batch, seq_len=sz.seq_len,
                 steps=sz.train_steps, first_loss=losses[0],
                 last_loss=losses[-1], step_s_median=step_s,
                 tokens_per_s=sz.batch * sz.seq_len / step_s)
        state.update(cfg=cfg, dot_first_loss=losses[0],
                     params=unstack_scan_params(
                         jax.device_get(trainer.train_state.params)))
    if "params" not in state:
        for name in ("serve", "kernels.paged_attention",
                     "kernels.quantized_matmul", "kernels.flash_attention",
                     "compile_cache"):
            run.skip(name, "train failed")
        return

    cfg, params = state["cfg"], state["params"]
    dec = TransformerLM(dataclasses.replace(
        cfg, decode=True, scan_layers=False, scan_unroll=1, remat=False,
        remat_policy=None))
    reqs = make_requests(sz)
    greedy_rows = [i for i, kw in enumerate(reqs)
                   if kw["temperature"] == 0.0]

    with run.phase("serve") as f:
        tokens, _, ticks = serve(dec, params, reqs, sz)
        P = sz.prefill_len
        batch = np.zeros((len(reqs), P), np.int32)
        lengths = np.array([len(kw["prompt"]) for kw in reqs], np.int32)
        for r, kw in enumerate(reqs):
            batch[r, :lengths[r]] = kw["prompt"]
        ref = np.asarray(generate(dec, params, batch,
                                  max_new_tokens=sz.max_new,
                                  rng=jax.random.PRNGKey(0), temperature=0.0,
                                  prompt_lengths=lengths))
        ref_tokens = [[int(t) for t in ref[r, L:L + sz.max_new]]
                      for r, L in enumerate(lengths)]
        agree, first = compare_tokens(tokens, ref_tokens, greedy_rows)
        f.update(requests=len(reqs), greedy_rows=len(greedy_rows),
                 tokens=sum(len(t) for t in tokens), ticks=ticks,
                 greedy_equal_generate=agree, first_diff=first)
        check(agree, f"engine/generate() greedy mismatch at {first}")

    def pair(name, needle_required, base_kw, flag, probe_models, kv_dtype,
             probe_params, extra_probes=()):
        """One xla-vs-pallas engine pair + its teacher-forced probe(s);
        probe params arrive as thunks so building them is part of the
        phase."""
        with run.phase(name) as f:
            xla_tokens, xla_text, _ = serve(dec, params, reqs, sz,
                                            **base_kw, **{flag: "xla"})
            pal_tokens, pal_text, _ = serve(dec, params, reqs, sz,
                                            **base_kw, **{flag: "pallas"})
            agree, first = compare_tokens(xla_tokens, pal_tokens,
                                          greedy_rows)
            holds = "tpu_custom_call" in pal_text
            f.update(engines=[f"{flag}=xla", f"{flag}=pallas"],
                     greedy_tokens_agree=agree, first_diff=first,
                     kernel_in_pallas_program=holds,
                     kernel_in_xla_program="tpu_custom_call" in xla_text)
            f.update(probe_pair(probe_models, probe_params(), sz, kv_dtype,
                                needle_required))
            for prefix, extra_params in extra_probes:
                f.update(probe_pair(probe_models, extra_params(), sz,
                                    kv_dtype, needle_required, prefix))
            if needle_required:
                check(holds, f"{flag}='pallas' ran without its kernel "
                             "(no tpu_custom_call in the step program)")

    def with_cfg(**kw):
        return TransformerLM(dataclasses.replace(dec.cfg, **kw))

    pair("kernels.paged_attention", on_tpu,
         dict(page_size=sz.page_size, page_native=True, kv_dtype="int8"),
         "attention_kernel",
         (with_cfg(attention_kernel="xla"),
          with_cfg(attention_kernel="pallas")), "int8", lambda: params)
    # int4 has no engine here (ISSUE: engines are the slow part) but its
    # kernel runs on the chip through the same probe
    pair("kernels.quantized_matmul", on_tpu, dict(weight_dtype="int8"),
         "matmul_kernel",
         (with_cfg(matmul_kernel="xla"), with_cfg(matmul_kernel="pallas")),
         None, lambda: quantize_params(params, "int8"),
         extra_probes=[("int4_probe", lambda: quantize_params(
             params, "int4", group_size=sz.int4_group))])

    with run.phase("kernels.flash_attention") as f:
        # the dense path for comparison: on a TPU the default seat takes
        # the kernel by itself at this length (models/transformer.py::
        # attention_seat), so the smoke steers it aside for one fit
        from ray_lightning_tpu.models import transformer
        seat = transformer.attention_seat
        transformer.attention_seat = lambda *a, **k: (False, "smoke")
        try:
            _, _, dlosses, _ = fit(sz, train_config(sz), strategy(), 2)
        finally:
            transformer.attention_seat = seat
        fcfg = train_config(sz, attention_impl="flash")
        ftrainer, fmodule, flosses, _ = fit(sz, fcfg, strategy(), 2)
        text = train_step_text(ftrainer, fmodule)
        holds = "tpu_custom_call" in text
        diff = abs(flosses[0] - dlosses[0])
        default_diff = abs(state["dot_first_loss"] - dlosses[0])
        f.update(steps=2, flash_first_loss=flosses[0],
                 dot_first_loss=dlosses[0],
                 default_seat_first_loss=state["dot_first_loss"],
                 first_loss_abs_diff=diff,
                 default_seat_abs_diff=default_diff, tolerance=FLASH_TOL,
                 kernel_in_train_program=holds)
        check(all(np.isfinite(flosses)), flosses)
        check(diff <= FLASH_TOL, (flosses[0], dlosses[0]))
        check(default_diff <= FLASH_TOL,
              (state["dot_first_loss"], dlosses[0]))
        if on_tpu:
            check(holds, "attention_impl='flash' trained without the "
                         "Pallas kernel (no tpu_custom_call)")

    with run.phase("compile_cache") as f:
        # a second in-script build of one program: the first build writes
        # the persistent cache, the second (after dropping the in-memory
        # caches) must be served from it
        toks = np.zeros((1, sz.prefill_len), np.int32)
        fwd_model = TransformerLM(dataclasses.replace(dec.cfg, decode=False))

        def build():
            t0 = time.perf_counter()
            jax.jit(lambda p, t: fwd_model.apply({"params": p}, t)).lower(
                params, toks).compile()
            return time.perf_counter() - t0

        h0 = run.meter.cache_hits
        first_s = build()
        jax.clear_caches()
        second_s = build()
        hits = run.meter.cache_hits - h0
        f.update(first_build_s=round(first_s, 3),
                 second_build_s=round(second_s, 3), hits_on_rebuild=hits)
        check(hits >= 1, "the rebuild did not hit the persistent cache")


# --------------------------------------------------------------------- #
# the four-chip path (and what it is compared with) — nothing else
# --------------------------------------------------------------------- #
def shard_report(tree) -> dict:
    """Where a pytree of jax arrays really lives: distinct devices, and
    each device's share of the bytes."""
    import jax
    per_device, total = {}, 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_device[sh.device.id] = (per_device.get(sh.device.id, 0)
                                        + sh.data.nbytes)
    shares = {d: b / total for d, b in sorted(per_device.items())}
    return dict(devices=sorted(per_device), total_bytes=total,
                share_per_device=[round(s, 4) for s in shares.values()])


def four_chips(sz: Sizes, run: Run) -> None:
    import numpy as np

    import ray_lightning_tpu as rlt
    on_tpu = run.on_tpu
    cfg = train_config(sz)
    steps = sz.multichip_steps
    state = {}
    with run.phase("multichip.fsdp4") as f:
        trainer, _, losses, _ = fit(
            sz, cfg, rlt.FSDPStrategy(num_workers=4, use_tpu=on_tpu), steps)
        check(len(losses) == steps and all(np.isfinite(losses)), losses)
        params = shard_report(trainer.train_state.params)
        opt = shard_report(trainer.train_state.opt_state)
        f.update(strategy="FSDPStrategy(num_workers=4)", steps=steps,
                 losses=losses, mesh=dict(trainer.strategy.mesh.shape),
                 params=params, opt_state=opt)
        for name, rep in (("params", params), ("opt_state", opt)):
            check(len(rep["devices"]) == 4, (name, rep))
            # about a quarter each: biases / LayerNorm vectors too small
            # to split replicate, so a share may sit slightly above 0.25
            check(all(0.2 <= s <= 0.3 for s in rep["share_per_device"]),
                  (name, rep))
        state["losses"] = losses
    with run.phase("multichip.one_device") as f:
        trainer, _, losses, _ = fit(
            sz, cfg, rlt.RayStrategy(num_workers=1, use_tpu=on_tpu), steps)
        params = shard_report(trainer.train_state.params)
        f.update(strategy="RayStrategy(num_workers=1)", steps=steps,
                 losses=losses, params=params)
        check(len(params["devices"]) == 1, params)
        diffs = [abs(a - b) for a, b in zip(state["losses"], losses)]
        f.update(loss_abs_diffs=diffs, tolerance=LOSS_TOL)
        check(len(diffs) == steps and max(diffs) <= LOSS_TOL, diffs)


# --------------------------------------------------------------------- #
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the four-chip path and its one-device "
                         "comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny-size walk of the control flow; CPU only")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    device = devices[0]
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(devices)}
    if args.rehearse:
        if device.platform != "cpu":
            print("--rehearse is the CPU walk-through; it never runs on an "
                  f"accelerator (found {info})", file=sys.stderr)
            return 2
    elif device.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; jax found {info}. Nothing was "
              "run and nothing is reported.", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"--chips {args.chips} needs exactly {args.chips} device(s); "
              f"jax found {info}", file=sys.stderr)
        return 2

    from ray_lightning_tpu import _native
    from ray_lightning_tpu.util import enable_compile_cache
    cache_dir = enable_compile_cache()
    run = Run(device, CompileMeter())
    meter = run.meter
    sz = TINY if args.rehearse else Sizes()
    emit(phase="start", rehearsal=args.rehearse, chips=args.chips, **info,
         jax=jax.__version__, compile_cache_dir=cache_dir,
         native_loaded=_native.native_available(), sizes=dataclasses.asdict(sz))
    ok = False
    try:
        (four_chips if args.chips == 4 else one_chip)(sz, run)
        ok = not run.failed
    finally:
        if run.failed:
            print(f"failed phases: {run.failed}", file=sys.stderr)
        emit(phase="end", rehearsal=args.rehearse, failed=run.failed,
             compile_s_total=round(meter.compile_s, 3),
             cache_hits=meter.cache_hits, cache_misses=meter.cache_misses)
        last = {"ok": ok, "device": info}
        if args.rehearse:
            last["rehearsal"] = True
        print(json.dumps(last), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
