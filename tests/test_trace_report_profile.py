"""``tools/trace_report.py --profile``: device time by scope and idle gaps
charged to the program's spans. The pure parts on hand-built intervals;
the loader on a real (CPU) profile of a few armed ticks, where XLA's CPU
ops stand in for the device plane."""
import importlib.util
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.trace_reduce import Ev

ROOT = pathlib.Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "tools_trace_report", ROOT / "tools" / "trace_report.py")
report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(report)

DECODE = ("jit(_engine_step_impl)/jit(main)/while/body/decode/forward/"
          "TransformerLM/stack/block_17/attn/")


@pytest.mark.parametrize("op_name,depth,expected", [
    (DECODE + "attention/scores/dot_general", 2,
     "decode/…/attention/scores"),
    (DECODE + "attention/scores/dot_general", 4,
     "decode/…/block_*/attn/attention/scores"),
    (DECODE + "kv_write/dynamic_update_slice", 1, "decode/…/kv_write"),
    ("jit(_engine_step_impl)/jit(main)/while/body/sample/top_k/sort", 2,
     "sample/top_k"),
    # as the chip's profiler writes them (PR 26's serve profile): the
    # einsum's spec, vmap shells and cond branches are no scopes
    ("jit(_engine_step_impl)/while/body/closed_call/decode/forward/"
     "TransformerLM/stack/block_18/attn/attention/context/"
     "bhqk,bkhd->bqhd/dot_general", 2, "decode/…/attention/context"),
    ("jit(_engine_step_impl)/while/body/closed_call/cond/branch_1_fun/"
     "cond/branch_1_fun/vmap(sample/top_k)/jit(argsort)/sort", 2,
     "sample/top_k"),
    (DECODE + "attn._decode_cache/kv_write/vmap(vmap())/scatter", 2,
     "decode/…/attn._decode_cache/kv_write"),
    ("jit(step)/jit(main)/transpose(jvp(loss))/TransformerLM/stack/while/"
     "body/checkpoint/layers/block/attn/attention/softmax/mul", 2,
     "transpose(jvp(loss))/…/attention/softmax"),
    ("jit(step)/jit(main)/optimizer/mul", 2, "optimizer"),
    ("jit(step)/jit(main)/add", 2, "(unscoped)"),
    ("", 2, "(unscoped)"),
])
def test_scope_of_keeps_the_first_and_the_last_components(op_name, depth,
                                                          expected):
    assert report.scope_of(op_name, depth) == expected


def test_gaps_are_charged_to_the_span_that_owns_most_of_each():
    ms = 1e6
    host = [Ev(0, 100 * ms, "serve.tick"),
            Ev(10 * ms, 20 * ms, "scheduler.plan"),
            Ev(20 * ms, 30 * ms, "engine.step.build"),
            Ev(30 * ms, 90 * ms, "engine.step.sync"),
            Ev(120 * ms, 200 * ms, "serve.tick"),
            Ev(121 * ms, 125 * ms, "serve.sweep")]
    gaps = [(12 * ms, 45 * ms),     # 8 plan, 10 build, 15 sync
            (92 * ms, 99 * ms),     # the first tick's own time
            (100 * ms, 124 * ms)]   # 20 between ticks, 1 tick, 3 sweep
    named, by_self = report.charge_gaps(gaps, host)
    assert [n for n, _ in named] == [
        "engine.step.sync", "serve.tick", "(outside every program span)"]
    assert named[0][1] == pytest.approx(0.033)
    assert by_self["scheduler.plan"] == pytest.approx(0.008)
    assert by_self["engine.step.build"] == pytest.approx(0.010)
    assert by_self["engine.step.sync"] == pytest.approx(0.015)
    assert by_self["serve.tick"] == pytest.approx(0.007 + 0.001)
    assert by_self["serve.sweep"] == pytest.approx(0.003)


def _pb(*fields):
    """A protobuf message from ``(number, int | bytes)`` pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    out = b""
    for no, value in fields:
        if isinstance(value, int):
            out += varint(no << 3) + varint(value)
        else:
            out += varint(no << 3 | 2) + varint(len(value)) + value
    return out


def test_op_names_are_read_from_the_event_metadata_of_an_xplane(tmp_path):
    """The ``tf_op`` stat of an event's *metadata* (which ``ProfileData``
    does not hand out) is read from the file's own bytes; lines are
    skipped, planes without the stat give nothing."""
    def event_meta(key, name, *stats):
        meta = _pb((1, key), (2, name), *[(5, st) for st in stats])
        return (4, _pb((1, key), (2, meta)))

    tf_op = (5, _pb((1, 26), (2, _pb((1, 26), (2, b"tf_op")))))
    other = (5, _pb((1, 3), (2, _pb((1, 3), (2, b"flops")))))
    device = _pb(
        (2, b"/device:TPU:0"),
        (3, _pb((2, b"XLA Ops"), (4, _pb((1, 7), (3, 1000))))),   # a line
        event_meta(7, b"%fusion.1 = bf16[4] fusion()",
                   _pb((1, 3), (3, 99)),
                   _pb((1, 26), (5, b"jit(f)/attention/scores/dot"))),
        event_meta(8, b"%copy.2 = f32[8] copy()", _pb((1, 3), (3, 5))),
        tf_op, other)
    host = _pb((2, b"/host:CPU"), event_meta(1, b"serve.tick"), other)
    path = tmp_path / "a.xplane.pb"
    path.write_bytes(_pb((1, device), (1, host)))
    assert report.op_names_by_event(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = bf16[4] fusion()": "jit(f)/attention/scores/dot"}}


@pytest.fixture(scope="module")
def profile_dir(tmp_path_factory):
    from ray_lightning_tpu.models.gpt import gpt2_config
    from ray_lightning_tpu.models.transformer import TransformerLM
    from ray_lightning_tpu.obs import Telemetry
    from ray_lightning_tpu.serve import ServeClient
    mk = dict(vocab_size=128, max_seq_len=64, dtype=jnp.float32,
              scan_layers=False)
    dec = TransformerLM(gpt2_config("nano", decode=True, **mk))
    params = TransformerLM(gpt2_config("nano", **mk)).init(
        jax.random.PRNGKey(0), np.zeros((2, 4), np.int32))["params"]
    client = ServeClient(dec, params, num_slots=2, prefill_len=8,
                         clock=time.perf_counter,
                         telemetry=Telemetry(clock=time.perf_counter))

    def drive():
        client.submit([5, 17, 3], max_new_tokens=4)
        client.run_until_idle()

    drive()                                   # compile outside the profile
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        drive()
    finally:
        jax.profiler.stop_trace()
    return out


def test_profile_report_reads_spans_and_ops_of_a_real_profile(profile_dir):
    doc = report.profile_report(profile_dir, depth=2, top=5, min_gap_ms=0.0)
    assert {"serve.tick", "engine.step.call", "engine.step.sync",
            "engine.prefill.call"} <= set(doc["host_span_names"])
    dev, = doc["devices"]
    assert dev["by_scope"] and dev["busy_s"] > 0
    assert 0.0 <= dev["idle_share"] <= 1.0
    gaps = dev["gaps"]
    assert gaps["count"] > 0
    # the idle time inside program spans is charged to named spans
    assert any(name.split(".")[0] in ("serve", "engine", "scheduler")
               for name, _ in gaps["idle_by_span_self_time"])
    text = report.format_profile_report(doc)
    assert "device self time by scope" in text and "idle gaps" in text


def test_cli_prints_the_profile_report(profile_dir, capsys):
    assert report.main(["--profile", profile_dir, "--top", "3"]) == 0
    out = capsys.readouterr().out
    assert "program span events on the host plane" in out
    with pytest.raises(SystemExit):
        report.main([])
