"""The benchmark's own tests run on the CPU at sizes a test can hold:
four virtual devices stand in for the 2x2 host."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

TINY_LM = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, intermediate_size=96, vocab_size=512,
               num_hidden_layers=2)


def tiny(name: str):
    """The cell with its widths, rows and messages cut to a test's size."""
    from bench import harness
    cell = harness.load_cell(name)
    kind = cell.traffic["kind"]
    if kind in ("train", "serve"):
        cell.config.update(TINY_LM)
    if kind == "train":
        cell.traffic.update(rows=2, seq=64)
    if kind == "collective":
        cell.traffic.update(message_mib=[1], cols=256)
    if kind == "serve":
        cell.traffic.update(
            lanes=4, s_ctx=64, rate_per_s=4.0, check_tokens=40, drain_s=30,
            ramp_s=1.0,
            prompt={"median": 8, "sigma": 0.5, "min": 2, "max": 40},
            output={"median": 6, "sigma": 0.5, "min": 2, "max": 24})
    return cell


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
         "ici_bytes_per_s": 1e10}


# Limits at the test's size, between what sound runs and the fp8 control
# read there on the CPU (the tiny model's bfloat16 rounding is a larger
# share than the full model's, so the cells' own limits would fail it).
TINY_LIMITS = {
    "train": {"loss_gap": 8e-4, "gnorm_gap": 8e-4, "grad_gap": 2.7e-3,
              "update_gap": 3.5e-3},
    "serve": {"logit_gap": 0.01},
    "collective": {"wrong_outputs": 0},
}


def run_tiny(cell, seed=2 ** 33 + 3, seconds=1.0):
    """The rest of a benchmark run, past the look for a chip."""
    import jax
    import bench.run as R
    return R.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                      devices=jax.devices()[:cell.chips], peaks=PEAKS,
                      limits=TINY_LIMITS[cell.traffic["kind"]])


@pytest.fixture
def tiny_cell():
    return tiny
