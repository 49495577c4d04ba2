"""``afmoe_work``'s operations and bytes against counts made by hand at
the published widths of ``trinity-large-preview``, and the two numbers
``afmoe_weights.ROUTER_BIAS_STD`` is set from."""
import numpy as np

from benchmark import afmoe_weights, afmoe_work, harness

SHAPE = harness.load_json("benchmark", "configs",
                          "trinity-large-preview.json")
ATTN = 3072 * 14336 + 6144 * 3072           # qkvg and o: 62 914 560
DENSE = 3 * 3072 * 12288                    # 113 246 208
EXPERT = 3 * 3072 * 3072                    # 28 311 552
ROUTER = 3072 * 256
HEAD = 3072 * 25024
FIXED = 5 * ATTN + DENSE + 4 * (ROUTER + EXPERT) + HEAD


def test_parameters_by_hand():
    assert afmoe_work.attention_params(SHAPE) == ATTN == 62914560
    assert afmoe_work.dense_mlp_params(SHAPE) == DENSE == 113246208
    assert afmoe_work.expert_params(SHAPE) == EXPERT == 28311552
    assert afmoe_work.router_params(SHAPE) == ROUTER
    assert afmoe_work.fixed_params(SHAPE) == FIXED == 621084672
    assert afmoe_work.experts_per_token(SHAPE) == 0.5
    # the configuration file's 4.32 B: everything held, the embedding too
    held = FIXED + 4 * 32 * EXPERT + HEAD
    assert round(held / 1e6) == 4322


def test_keys_follow_the_window():
    # a decoded token at context 5000: four rings of 4096, one full row
    assert afmoe_work.keys_read(SHAPE, 4999, 1) == 4 * 4096 + 5000
    # below the window every layer reads the context
    assert afmoe_work.keys_read(SHAPE, 99, 1) == 5 * 100
    # a piece of 512 at offset 4000 crosses the window's edge at 4096
    window = sum(range(4001, 4097)) + (4512 - 4096) * 4096
    full = sum(range(4001, 4513))
    assert window == 2092592 and full == 2179328
    assert afmoe_work.keys_read(SHAPE, 4000, 512) == 4 * window + full
    # a whole prompt below the window: n (n + 1) / 2 a layer
    assert afmoe_work.keys_read(SHAPE, 0, 512) == 5 * 512 * 513 // 2


def test_flops_by_hand():
    token = 2 * (FIXED + 4 * 0.5 * EXPERT)
    assert afmoe_work.token_flops(SHAPE) == token == 1355415552
    per_key = 4 * 48 * 128
    assert afmoe_work.per_key_flops(SHAPE) == per_key
    assert afmoe_work.decode_flops(SHAPE, 5000) \
        == token + per_key * (4 * 4096 + 5000) == 1880948736
    piece = 512 * (token - 2 * HEAD) + per_key * afmoe_work.keys_read(
        SHAPE, 4000, 512)
    assert afmoe_work.piece_flops(SHAPE, 4000, 512) == piece
    assert afmoe_work.prefill_flops(SHAPE, 512) == 512 * (token - 2 * HEAD) \
        + per_key * 5 * 512 * 513 // 2 + 2 * HEAD


def test_bytes_by_hand():
    assert afmoe_work.kv_bytes_per_position(SHAPE) == 4096
    assert afmoe_work.live_kv_bytes(SHAPE, 5000) == 4096 * (4 * 4096 + 5000)
    # a slot's cache as held: four layers of rings, one of 8192 positions
    assert 4 * 4096 * 4096 + 4096 * 8192 == 100663296
    got = afmoe_work.decode_step_bytes(SHAPE, [5000, 100], 50)
    assert got == 2 * (FIXED + 50 * EXPERT) + 4096 * (4 * 4096 + 5000) \
        + 4096 * 500


def test_the_router_bias_moves_a_few_percent_of_the_choices():
    """At the published width: logits spread by ``0.02 sqrt(3072)``, the
    top four sigmoid scores saturate, and a choice bias of
    ``ROUTER_BIAS_STD`` moves the top four of about one token in
    sixteen."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 3072))
    x /= np.sqrt((x * x).mean(-1, keepdims=True))
    s = 1 / (1 + np.exp(-x @ (rng.normal(size=(3072, 256)) * 0.02)))
    ranked = np.sort(s, -1)[:, ::-1]
    assert 0.90 < ranked[:, 3].mean() < ranked[:, 0].mean() < 0.97
    assert 0.004 < np.median(ranked[:, 3] - ranked[:, 4]) < 0.008
    bias = rng.normal(size=256) * afmoe_weights.ROUTER_BIAS_STD
    plain = np.sort(np.argsort(-s, -1)[:, :4], -1)
    moved = np.sort(np.argsort(-(s + bias), -1)[:, :4], -1)
    assert 0.03 < (plain != moved).any(-1).mean() < 0.10
    # 32 rows x 4 experts over 256: half a row a held expert, 38 % hit
    loads = np.stack([np.bincount(plain[i:i + 32].ravel(),
                                  minlength=256)[:32]
                      for i in range(0, 2048, 32)])
    assert 0.3 < (loads > 0).mean() < 0.46
    assert 12 < loads.sum(-1).mean() < 20
