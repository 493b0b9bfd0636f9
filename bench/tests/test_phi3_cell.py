"""The Phi-3 train cell at a test's size: full MHA, and a window under
the row's length so that training takes the banded attention path. A
sound run is correct, and the cell's fp8 control fails its limits."""
from bench import compare
from bench.tools import calibrate
from conftest import run_tiny, tiny

CELL = "phi3-mini-3.8b.train-4k"


def phi3_tiny():
    cell = tiny(CELL)
    cell.config.update(num_key_value_heads=cell.config["num_attention_heads"],
                       sliding_window=16)
    cell.traffic.update(rows=1)
    return cell


def test_sound_run_is_correct():
    res = run_tiny(phi3_tiny())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_fp8_fails_a_limit():
    cell = phi3_tiny()
    nums = calibrate.train_variant(cell, 2 ** 33 + 1, "control")
    assert not compare.passed(compare.checks(nums, compare.limits(CELL))), nums
