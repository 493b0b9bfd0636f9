"""The training attention's device time and work (bench/attn.py), on
hand-made traces whose numbers are known."""
import pytest

from bench import attn, flops, trace


def planes(ops, window=(0, 100)):
    ops = [o if len(o) == 4 else o + ({},) for o in ops]
    return [("/device:TPU:0", {trace.OPS_LINE: ops}),
            ("/host:CPU", {"python3": [("bench.window",) + window + ({},)]})]


def test_tagged_ops_by_attribute_or_op_name():
    ops = [('%while.3 = (..) while(..), frontend_attributes={attn_scope='
            '"attn.fwd"}', 10, 40),
           ("%fusion.9 = f32[..] fusion(..), kind=kOutput", 20, 30),
           ("%fusion.2 = f32[..] fusion(..), kind=kOutput", 50, 60,
            {"long_name": "jit(step)/transpose(jvp(attn.bwd))/dot_general"}),
           ("%fusion.4 = f32[..] fusion(..), kind=kLoop", 60, 90),
           ('%fusion.5 = bf16[..] fusion(..), frontend_attributes={attn_scope='
            '"attn.bwd"}', 95, 120)]
    # the while covers its untagged body op; the last op is clipped at the
    # window's end; the untagged op after the window's tagged ones is not
    assert attn.reduce_planes(planes(ops)) == pytest.approx(
        (30 + 10 + 5) * 1e-9)


def test_no_tag_reads_none():
    ops = [("%fusion.9 = f32[..] fusion(..), kind=kOutput", 20, 30)]
    assert attn.reduce_planes(planes(ops)) is None
    assert attn.reduce_planes(planes([])[:1]) is None      # no window


def test_work_counts_the_keys_each_query_sees():
    c = {"num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 3}
    keys = sum(min(p + 1, 3) for p in range(10))           # 27
    assert attn.train_flops(c, 2, 10) == 2 * 3.5 * 2 * 4 * 4 * 8 * keys
    assert attn.train_flops(c, 1, 10) == 3.5 * flops.attention_flops_fwd(
        c, 10)
    per_tok = 2 * (6 * 4 * 8 + 6 * 2 * 8) + 2 * 4 * 4
    assert attn.train_bytes(c, 2, 10) == 2 * 2 * 10 * per_tok
