"""``correct`` has to come out false when the timed path is broken, and
the control (the reference one precision down, in the program's place) has
to read well above a sound run. Nano widths on the CPU: the limits are
those of the cells, set from chip readings (PERF.md)."""
import json

import pytest

from benchmark import harness, run

TRAIN1 = "gpt2-medium.train.b12-t1024"
SERVE = "gpt2-large.serve.closed40"
FSDP4 = "gpt2-xl.train.fsdp4"
CELLS = {w["name"] for w in harness.load_json("BENCHMARK.json")["workloads"]}


def drive(capsys, cell, seed=11, trace=0):
    """The rest of a run behind the look for a chip: the rehearsal."""
    if cell not in CELLS:
        pytest.skip(f"{cell} is not a cell of BENCHMARK.json")
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    return line


def keep_rows(monkeypatch, fraction):
    """The program's GPT module with only the first ``1/fraction`` of the
    batch in its loss, the mean taken over those rows."""
    from ray_lightning_tpu.models import GPTModule
    sound = GPTModule.training_step

    def partial(self, model, variables, batch, rng):
        x, y = batch
        n = x.shape[0] // fraction
        return sound(self, model, variables, (x[:n], y[:n]), rng)

    monkeypatch.setattr(GPTModule, "training_step", partial)


@pytest.mark.parametrize("cell,trace", [(TRAIN1, 0), (SERVE, 1), (FSDP4, 0)])
def test_sound_run_is_correct(capsys, cell, trace):
    line = drive(capsys, cell, trace=trace)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    assert all(v <= lim for v, lim in line["compared"].values())
    assert "setup_s" in line["metrics"] or trace


def test_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    from ray_lightning_tpu.strategies.base import Strategy
    sound = Strategy.make_train_step

    def broken(self, *a, **kw):
        step = sound(self, *a, **kw)
        return lambda state, batch: (state, step(state, batch)[1])

    monkeypatch.setattr(Strategy, "make_train_step", broken)
    line = drive(capsys, TRAIN1)
    assert not line["correct"]
    assert line["compared"]["param_change_gap"][0] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(capsys, monkeypatch):
    keep_rows(monkeypatch, 2)
    line = drive(capsys, TRAIN1)
    assert not line["correct"]
    value, limit = line["compared"]["grad_norm_gap"]
    assert value > limit


def test_exchange_between_chips_left_out(capsys, monkeypatch):
    # one chip applies the gradient of its own quarter of the rows
    keep_rows(monkeypatch, 4)
    line = drive(capsys, FSDP4)
    assert not line["correct"]
    value, limit = line["compared"]["grad_norm_gap"]
    assert value > limit


def alter_tokens(monkeypatch, sampled: bool):
    """Every completion of one kind of request (greedy, or sampled) comes
    back with its middle token replaced by the vocabulary's next."""
    from ray_lightning_tpu.serve import ServeClient
    tick, submit = ServeClient.tick, ServeClient.submit
    mine = set()

    def watched(self, *a, **kw):
        rid = submit(self, *a, **kw)
        if (kw["temperature"] > 0) == sampled:
            mine.add(rid)
        return rid

    def broken(self):
        done = tick(self)
        for comp in done:
            if comp.request_id in mine:
                mid = len(comp.tokens) // 2
                comp.tokens[mid] = (comp.tokens[mid] + 1) % 256
        return done

    monkeypatch.setattr(ServeClient, "submit", watched)
    monkeypatch.setattr(ServeClient, "tick", broken)


@pytest.mark.parametrize("sampled,number", [
    (False, "served_logit_gap"), (True, "sampled_topk_gap")])
def test_token_altered_where_it_is_produced(capsys, monkeypatch, sampled,
                                            number):
    alter_tokens(monkeypatch, sampled)
    line = drive(capsys, SERVE)
    assert not line["correct"]
    for name, (value, limit) in line["compared"].items():
        assert (value > limit) == (name == number), line["compared"]


def test_first_token_stamp_outside_the_callers_own_fails(capsys,
                                                         monkeypatch):
    """A first-token stamp earlier than the harness's own submit stamp is
    no time to first token: the request counts as failed."""
    from ray_lightning_tpu.serve import ServeClient
    sound = ServeClient.tick

    def broken(self):
        done = sound(self)
        for comp in done:
            comp.first_token_time = comp.arrival_time - 1.0
        return done

    monkeypatch.setattr(ServeClient, "tick", broken)
    line = drive(capsys, SERVE)
    assert not line["correct"] and line["failed"] == line["attempted"] > 0


def _ctx(cell):
    import argparse
    import time

    import jax
    bench = harness.load_json("BENCHMARK.json")
    args = argparse.Namespace(workload=cell, seed=5, seconds=1.0, trace=0,
                              rehearse=True)
    ctx = harness.Ctx(args, bench, time.perf_counter())
    ctx.devices = jax.devices()
    return ctx


def test_train_control_reads_above_a_sound_precision():
    """fp8 in the program's place against bf16 (what the configuration
    states) in the program's place: the control is at least three times
    worse on some number."""
    kind = harness.load_module("kinds", "train_fit")
    ctx = _ctx(TRAIN1)
    w, shape = ctx.workload, ctx.shape
    feed = kind.TokenFeed(ctx.seed, w["batch"], w["seq_len"],
                          shape["vocab_size"], w["data_pool_batches"])
    ref = kind.run_reference(ctx, feed, "f32")
    sound = kind.judge(kind.run_reference(ctx, feed, "bf16"), ref,
                       w["limits"], 3)
    control = kind.judge(kind.run_reference(ctx, feed, "fp8"), ref,
                         w["limits"], 3)
    assert any(control[k][0] >= 3 * sound[k][0] for k in control)
    assert not harness.compare(
        {k: (control[k][0], 2 * sound[k][0]) for k in control})


def test_serve_control_reads_above_a_sound_precision():
    """At nano widths and 256 tokens of vocabulary the margins are too wide
    for fp8 to flip an argmax, so the control is held by its logits here:
    fp8's error against the reference is over three times bf16's. The gap
    itself is read on the chip at the cell's own size (PERF.md)."""
    import jax
    import numpy as np

    from benchmark import reference, weights
    kind = harness.load_module("kinds", "serve_closed_loop")
    ctx = _ctx(SERVE)
    key = weights.seed_key(ctx.seed)
    rng = np.random.default_rng(0)
    sample = [dict(prompt=rng.integers(0, 256, 12).tolist(),
                   tokens=rng.integers(0, 256, 20).tolist(), greedy=True)
              for _ in range(4)]
    got = kind.served_gaps(ctx, key, sample, 20, control_mode="fp8")
    # random "served" tokens lie far below the reference's best
    assert got["positions"] == 80 and got["sampled"] is None
    assert got["greedy"] > ctx.workload["limits"]["served_logit_gap"]
    params = jax.jit(lambda k: weights.make_canonical(k, ctx.shape))(key)
    toks = rng.integers(0, 256, (1, 64)).astype(np.int32)
    f32, bf16, fp8 = (np.asarray(reference.make_logits_fn(ctx.shape, m)(
        params, toks)) for m in ("f32", "bf16", "fp8"))
    assert np.abs(fp8 - f32).max() >= 3 * np.abs(bf16 - f32).max() > 0
