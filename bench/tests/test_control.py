"""Each cell's control comes out not correct under the cell's limits: the
reference, one precision below what the configuration states, put in the
program's place (the same check the chip readings in ``limits/`` come
from, at a size a test can hold)."""
import jax

from bench import compare
from bench.tools import calibrate
from conftest import run_tiny, tiny


def test_train_control_fp8_fails_a_limit():
    cell = tiny("qwen3-1.7b.train-2k")
    nums = calibrate.train_variant(cell, 2 ** 33 + 1, "control")
    lim = compare.limits(cell.name)
    assert not compare.passed(compare.checks(nums, lim)), nums


def test_train_half_batch_fails_a_limit():
    cell = tiny("qwen3-1.7b.train-2k")
    nums = calibrate.train_variant(cell, 2 ** 33 + 2, "half_batch")
    lim = compare.limits(cell.name)
    assert not compare.passed(compare.checks(nums, lim)), nums


def test_coll_control_bf16_fails():
    cell = tiny("cube2x2.coll-bw")
    nums = calibrate.coll_control(cell, 5, jax.devices()[:4])
    assert nums["wrong_outputs"] > 0


def test_serve_control_fp8_reads_above_the_program():
    """A tiny model with a tied embedding puts the input token first by a
    wide margin at every position, so neither side ever ties; this reads
    the control with an untied head over a wider vocabulary, where the
    largest logits lie close as at full size. Its logits are a few times
    smaller than the full model's, so the limit in logits (set from the
    chip readings at full size) does not carry over: the test asks that
    the control read wider gaps than the program."""
    cell = tiny("qwen3-1.7b.serve-chat")
    cell.config.update(vocab_size=32768, num_hidden_layers=4,
                       hidden_size=128, intermediate_size=256,
                       tie_word_embeddings=False)
    nums = calibrate.serve_control(cell, 3, jax.devices()[:1], 2.0)
    assert nums["logit_gap"] > 2 * nums["program_logit_gap"], nums
    assert nums["logit_gap"] > 0, nums
