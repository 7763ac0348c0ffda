"""Operation and byte counts against hand counts at OLMo-1B's widths and at
Yi-6B's with 8 layers."""
from bench import counts
from bench.tests.test_bench_reference import config


def dims(name):
    return counts.Dims.of(config(name))


def test_olmo_1b_hand_counts():
    d = dims("olmo-1b")
    # per layer: q,k,v,o 4 x 2048 x 2048, MLP 3 x 2048 x 8192
    assert d.layer_params() == 4 * 2048 * 2048 + 3 * 2048 * 8192 == 67108864
    assert d.linear_flops() == 2 * 16 * 67108864 == 2147483648
    assert d.head_flops() == 2 * 2048 * 50304 == 206045184
    # scores and weighted values: 4 x 16 heads x 128 x 16 layers per key
    assert d.attn_flops(1) == 131072
    assert d.token_flops(1000) == 2147483648 + 131072000 + 206045184
    assert d.kv_bytes_per_token() == 2 * 16 * 16 * 128 * 2 == 131072
    assert d.weight_bytes() == (16 * 67108864 + 2048 * 50304) * 2


def test_yi_6b_8_layer_hand_counts():
    d = dims("yi-6b")
    # q and o 4096 x 4096; k and v 4096 x 512 (4 KV heads); MLP 3 x 4096 x 11008
    per = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert d.layer_params() == per == 173015040
    assert d.linear_flops() == 2 * 8 * per
    assert d.head_flops() == 2 * 4096 * 64000
    assert d.attn_flops(1) == 4 * 32 * 128 * 8
    assert d.kv_bytes_per_token() == 2 * 8 * 4 * 128 * 2 == 16384
    assert d.weight_bytes() == (8 * per + 4096 * 64000) * 2


def test_request_flops_sum_per_token_counts():
    d = dims("olmo-1b")
    p, s = 37, 5
    pre, dec = d.request_flops(p, s)
    assert pre == sum(d.token_flops(i + 1, head=False) for i in range(p)) \
        + d.head_flops()
    assert dec == sum(d.token_flops(p + j + 1) for j in range(s - 1))
    assert d.decode_bytes(3, [10, 20]) == \
        3 * d.weight_bytes() + (30 + 2) * d.kv_bytes_per_token()
