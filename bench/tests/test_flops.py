"""The operation and byte counts against hand counts."""
import json

import pytest

from bench import flops
from bench.harness import BENCH

MiB = 2 ** 20


def cfg(name):
    return json.load(open(BENCH / "configs" / f"{name}.json"))


def test_qwen3_layer_and_head():
    c = cfg("qwen3-1.7b-8L")
    # wq 2048x2048, wkv 2048x(8*2*128), wo 2048x2048, 3 x 2048x6144
    assert flops.layer_matmul_params(c) == 3 * 2048 * 2048 + 3 * 2048 * 6144
    assert flops.matmul_params(c) == 8 * 50_331_648 + 2048 * 151_936


def test_train_flops_per_token_qwen3():
    c = cfg("qwen3-1.7b-8L")
    # 6 x 713,818,112 weights, plus 3 x 4 x 16 x 128 x mean keys 1024.5
    # x 8 layers of causal attention over rows of 2048
    want = 6 * 713_818_112 + 3 * 4 * 16 * 128 * 1024.5 * 8
    assert flops.train_flops_per_token(c, 2048) == pytest.approx(want)


def test_sliding_window_caps_the_keys():
    c = dict(cfg("qwen3-1.7b"), sliding_window=2047)
    assert flops.keys_seen(0, c) == 1
    assert flops.keys_seen(2046, c) == 2047
    assert flops.keys_seen(4095, c) == 2047


def test_decode_step_counts_by_hand():
    c = dict(cfg("qwen3-1.7b"), num_hidden_layers=1, vocab_size=10,
             hidden_size=4, intermediate_size=8, num_attention_heads=2,
             num_key_value_heads=1, head_dim=2)
    w = 4 * 4 + 4 * 4 + 4 * 4 + 3 * 4 * 8 + 4 * 10      # 184
    # two lanes seeing 3 and 5 keys: 2 x 2w flops, 4 x 2 x 2 per key
    assert flops.decode_step_flops(c, [3, 5]) == 2 * 2 * w + 16 * 8
    # weights + norms (2 per layer + final + 2 qk norms of 2) + 2 rows,
    # in bf16; keys and values of 8 positions: 1 layer x 2 x 1 x 2 each
    nbytes = 2 * (w + 2 * 4 + 4 + 2 * 2 + 2 * 4) + 2 * 4 * 8
    assert flops.decode_step_bytes(c, [3, 5]) == nbytes


@pytest.mark.parametrize("prim,g,inp,out,want", [
    ("all_reduce", 4, 64 * MiB, 64 * MiB, 96 * MiB),
    ("reduce_scatter", 2, 16 * MiB, 8 * MiB, 8 * MiB),
    ("all_gather", 4, 4 * MiB, 16 * MiB, 12 * MiB),
    ("all_to_all", 4, 16 * MiB, 16 * MiB, 12 * MiB),
])
def test_bus_bytes(prim, g, inp, out, want):
    assert flops.bus_bytes(prim, g, inp, out) == want
