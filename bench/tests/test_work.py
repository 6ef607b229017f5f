"""``bench/work`` against counts made by hand for both configurations."""
import pytest

from bench import cell as cells

WORK = cells.load_module("work", "dense_decoder")


def _sizes(config):
    return WORK.sizes(cells.resolve(
        {"qwen3_8b.af8": "qwen3_8b.chat",
         "granite_34b.af8.l22": "granite_34b.long_gen"}[config]).config)


# per layer: q, k, v, o projections plus the MLP; the head; KV bytes
HAND = {
    # 4096*128*(32+8+8+32) + 3*4096*12288 = 41,943,040 + 150,994,944
    "qwen3_8b.af8": dict(layer=192_937_984, head=4096 * 151936, layers=36,
                         kv_token=36 * 2 * 8 * 128 * 2, heads=32, hd=128),
    # 6144*128*(48+1+1+48) + 2*6144*24576 = 77,070,336 + 301,989,888
    "granite_34b.af8.l22": dict(layer=379_060_224, head=6144 * 49152,
                                layers=22, kv_token=22 * 2 * 1 * 128 * 2,
                                heads=48, hd=128),
}


@pytest.mark.parametrize("config", sorted(HAND))
def test_sizes_by_hand(config):
    s, h = _sizes(config), HAND[config]
    assert s.layer_matmul_params == h["layer"]
    assert s.matmul_params == h["layers"] * h["layer"] + h["head"]
    assert s.weight_bytes == s.matmul_params          # AF8: one byte each
    assert s.kv_bytes_per_token == h["kv_token"]


@pytest.mark.parametrize("config", sorted(HAND))
def test_decode_call_by_hand(config):
    s, h = _sizes(config), HAND[config]
    rows = [(1, 100)] * 16                 # 16 tokens, each at length 100
    w = WORK.call_work(s, rows)
    mm_flops, mm_bytes = w["packed_matmul"]
    assert mm_flops == 2 * 16 * (h["layers"] * h["layer"] + h["head"])
    assert mm_bytes == h["layers"] * h["layer"] + h["head"]
    at_flops, at_bytes = w["paged_attention"]
    assert at_flops == 4 * h["layers"] * h["heads"] * h["hd"] * 100 * 16
    assert at_bytes == 16 * 7 * 16 * h["kv_token"]     # ceil(100/16) = 7
    flops, nbytes = w["step"]
    assert flops == mm_flops + at_flops
    d = {"qwen3_8b.af8": 4096, "granite_34b.af8.l22": 6144}[config]
    assert nbytes == mm_bytes + at_bytes + 16 * d + 16 * h["kv_token"]


def test_prefill_chunk_is_one_weight_pass():
    s = _sizes("qwen3_8b.af8")
    one = WORK.call_work(s, [(16, 48)])
    # 16 tokens at positions 33..48 attend over 33..48 rows: 648 in all
    assert one["paged_attention"][0] == 4 * 36 * 32 * 128 * 648
    assert one["packed_matmul"][1] == s.weight_bytes
    assert WORK.call_work(s, [])["packed_matmul"][0] == 0
