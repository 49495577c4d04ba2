"""``falcon_h1_work.py`` against counts made by hand for Falcon-H1-34B as
the benchmark runs it (6 of 72 layers, published widths, the whole
vocabulary), and the figures the configuration file and the cell's
sizing state."""
from benchmark import falcon_h1_work, harness

SHAPE = harness.load_json("benchmark", "configs",
                          "falcon-h1-34b-instruct.json")
# q 5120 x 2560, k and v 5120 x 512 each (one matrix), o 2560 x 5120
ATTN = 5120 * (2560 + 2 * 512) + 2560 * 5120
# in-projection z 4096 | x 4096 | B 2 x 256 | C 2 x 256 | dt 32, out 4096
SSM = 5120 * 9248 + 4096 * 5120
MLP = 3 * 5120 * 21504
# two RMSNorm gains, conv taps and bias over x | B | C, A_log D dt_bias,
# the gated norm's gain
VECTORS = 2 * 5120 + 4 * 5120 + 5120 + 3 * 32 + 4096
HEAD = 261120 * 5120
STATE = 32 * 128 * 256 + 3 * 5120          # SSM state and conv tail, floats
STEP_RULE = 6 * (5 * 32 * 128 * 256 + 2 * 4 * 5120)
CHUNK_RULE = 6 * (32 * (4 * 128 * 256 + 128 * 128) + 2 * 128 * 256
                  + 2 * 4 * 5120)
PER_KEY = 4 * 20 * 128 * 6


def test_parameters_by_hand():
    assert falcon_h1_work.attention_params(SHAPE) == ATTN == 31_457_280
    assert falcon_h1_work.ssm_params(SHAPE) == SSM == 68_321_280
    assert falcon_h1_work.mlp_params(SHAPE) == MLP == 330_301_440
    layer = ATTN + SSM + MLP + VECTORS
    assert falcon_h1_work.layer_params(SHAPE) == layer == 430_120_032
    # the configuration file's 5.255 B, 10.51 GB in bfloat16
    total = 6 * layer + 2 * HEAD + 5120
    assert falcon_h1_work.total_params(SHAPE) == total == 5_254_594_112
    assert round(2 * total / 1e7) == 1051
    assert falcon_h1_work.matmul_params(SHAPE) \
        == 6 * (ATTN + SSM + MLP) + HEAD
    assert falcon_h1_work.matmul_params(SHAPE, with_head=False) \
        == 6 * (ATTN + SSM + MLP)
    # at the published depth: 72 layers and the two tables, 34 B
    assert 33.6e9 < 72 * layer + 2 * HEAD < 33.7e9


def test_flops_by_hand():
    assert falcon_h1_work.ssd_flops_per_token(SHAPE, False) == STEP_RULE
    assert falcon_h1_work.ssd_flops_per_token(SHAPE, True) == CHUNK_RULE
    assert falcon_h1_work.per_key_flops(SHAPE) == PER_KEY
    weights = falcon_h1_work.matmul_params(SHAPE)
    assert falcon_h1_work.decode_flops(SHAPE, 1370) \
        == 2 * weights + STEP_RULE + PER_KEY * 1370
    body = weights - HEAD
    # a piece of 512 at offset 1024: keys 1025 .. 1536, no head
    assert falcon_h1_work.piece_flops(SHAPE, 1024, 512) == (
        2 * body * 512 + CHUNK_RULE * 512
        + PER_KEY * sum(range(1025, 1537)))
    # pieces add up to the prompt they make, but for the head
    whole = falcon_h1_work.prefill_flops(SHAPE, 1300) - 2 * HEAD
    parts = sum(falcon_h1_work.piece_flops(SHAPE, off, n)
                for off, n in ((0, 512), (512, 512), (1024, 276)))
    assert parts == whole
    # ~5.2 GFLOP a prompt token before attention
    assert 5.1e9 < 2 * body + CHUNK_RULE < 5.3e9


def test_cache_bytes_by_hand():
    row = falcon_h1_work.cache_bytes_per_row(SHAPE, 3072)
    assert row == {"recurrent": 6 * 4 * STATE, "window": 0,
                   "global": 6 * 2 * 4 * 128 * 2 * 3072}
    # a layer's SSM state a row: 32 x 128 x 256 float32, 4.19 MB
    assert 4 * 32 * 128 * 256 == 4_194_304
    assert round(row["recurrent"] / 1e4) == 2553        # 25.53 MB
    assert row["global"] == 37_748_736                  # 37.75 MB
    # a slot 63.28 MB, 48 of them 3.04 GB; with the weights 13.55 GB
    slot = row["recurrent"] + row["global"]
    assert round(slot / 1e4) == 6328
    assert round(48 * slot / 1e7) == 304
    assert round((48 * slot + 2 * falcon_h1_work.total_params(SHAPE))
                 / 1e7) == 1355


def test_decode_step_bytes_by_hand():
    """The 48-row step at a mean context of 1370: 7.83 GB of weights (the
    head 2.67 GB of them), 2.45 GB of state read and written, 0.81 GB of
    live K/V — 11.09 GB."""
    weights = 2 * falcon_h1_work.matmul_params(SHAPE)
    assert falcon_h1_work.decode_step_bytes(SHAPE, []) == weights
    assert round(weights / 1e7) == 783 and round(2 * HEAD / 1e7) == 267
    state = 2 * 6 * 4 * STATE
    kv = 6 * 2 * 4 * 128 * 2 * 1370
    got = falcon_h1_work.decode_step_bytes(SHAPE, [1370] * 48)
    assert got == weights + 48 * (state + kv)
    assert round(48 * state / 1e7) == 245 and round(48 * kv / 1e7) == 81
    assert round(got / 1e7) == 1109
    assert 0.24 < 2 * HEAD / got < 0.25
