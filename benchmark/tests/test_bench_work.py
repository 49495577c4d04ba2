"""``work.py`` against counts made by hand for gpt2-medium."""
import json
import os

import pytest

from benchmark import peaks, work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MEDIUM = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "gpt2-medium.json")))


def test_matmul_params():
    # 24 layers x 12 x 1024^2, plus the tied head 50257 x 1024
    assert work.matmul_params(MEDIUM, with_head=False) == 301_989_888
    assert work.matmul_params(MEDIUM) == 353_453_056


def test_train_flops_per_token():
    # 6 x 353,453,056 + 6 x 24 x 1024 x 1024
    assert work.train_flops_per_token(MEDIUM, 1024) == 2_271_713_280


def test_prefill_and_decode_flops():
    # one token: blocks + head + attention over itself
    one = 2 * 301_989_888 + 2 * 50257 * 1024 + 2 * 24 * 1024 * 1 * 2
    assert work.prefill_flops(MEDIUM, 1) == one
    assert work.decode_flops(MEDIUM, 1) == 2 * 353_453_056 + 4 * 24 * 1024
    # a prompt of P tokens costs what P decodes over contexts 1..P would,
    # less the P - 1 heads nobody reads
    p = 7
    assert work.prefill_flops(MEDIUM, p) == pytest.approx(
        sum(work.decode_flops(MEDIUM, c) for c in range(1, p + 1))
        - (p - 1) * 2 * 50257 * 1024)


def test_decode_step_bytes():
    assert work.kv_bytes_per_token(MEDIUM, 2) == 2 * 24 * 1024 * 2
    got = work.decode_step_bytes(MEDIUM, [100, 28], 4, 2)
    assert got == 353_453_056 * 4 + 128 * 98_304


def test_peaks_know_the_v5e_and_nothing_else():
    assert peaks.peak_flops("TPU v5 lite") == 197e12
    assert peaks.hbm_bandwidth("TPU v5e") == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_flops("cpu")


def test_slice_work_from_two_readings_of_the_token_frontier():
    """The serve kind's count of what a traced slice did, against a hand
    count: request 1 was mid-decode, 2 was admitted and retired inside
    the slice, 3 was admitted inside it, 4 did not move, 5 was still
    queued when it closed."""
    from benchmark import harness
    kind = harness.load_module("kinds", "serve_closed_loop")
    got = kind.slice_work(before={1: 5, 4: 9}, after={1: 8, 2: 3, 3: 1, 4: 9},
                          prompt_len={1: 10, 2: 20, 3: 30, 4: 40, 5: 50})
    assert got == {
        "prefills": 2, "prefill_tokens": 20 + 30,
        "prefill_sq": 20 * 21 + 30 * 31,
        # request 1: tokens 5, 6, 7; request 2: tokens 1, 2
        "decode_tokens": 3 + 2,
        "decode_context_sum": (15 + 16 + 17) + (21 + 22)}


def test_shape_pool_offers_every_seed_the_same_sizes():
    import json

    import numpy as np

    from benchmark import harness
    kind = harness.load_module("kinds", "serve_closed_loop")
    w = harness.load_json("benchmark", "workloads",
                          "gpt2-large.serve.closed40.json")
    a, b = (kind.shape_pool(w, 1024, np.random.default_rng([seed, 2]))
            for seed in (1, 2**31 + 5))
    assert a != b and len(a) == w["pool_requests"]
    for col in (0, 1):
        assert sorted(x[col] for x in a) == sorted(x[col] for x in b)
    prompts = sorted(x[0] for x in a)
    assert prompts[0] >= w["prompt"]["min"] and prompts[-1] == w["prompt"]["max"]
    assert abs(np.median(prompts) - w["prompt"]["median"]) <= 3
    assert all(p + o <= 1024 for p, o in a)
    assert json.dumps(a)        # plain ints

