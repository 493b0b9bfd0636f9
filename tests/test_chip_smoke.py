"""``chip_smoke.py``'s phases at tiny widths on the CPU substrate (the
script itself refuses to run off TPU), plus where the entry points put
the persistent compilation cache. These check control flow and results,
not the chip: the script's real run is on a v5e."""
import dataclasses
import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(n_layers, **kw):
    from repro import configs
    return dataclasses.replace(configs.get(chip_smoke.ARCH).scaled_for_smoke(),
                               n_layers=n_layers, **kw)


def test_train_phase_loss_falls():
    import jax
    # 64-wide layers need a larger step than the published widths for the
    # unigram signal to beat batch-to-batch noise within 6 steps
    r = chip_smoke.train_phase(_tiny(4), batch=1, seq=128, steps=6, lr=1e-2,
                               devices=jax.devices()[:1])
    chip_smoke.check_train(r)
    assert len(r["step_wall_s"]) == 6 and r["param_devices"] == 1


def test_serve_phase_completes_and_logits_agree():
    import jax
    r = chip_smoke.serve_phase(_tiny(28), n_requests=4, slots=2, s_ctx=96,
                               prompt_len=16, devices=jax.devices()[:1])
    assert r["requests"] == 4
    assert r["logit_rel_err"] <= chip_smoke.LOGIT_RTOL


def test_primitives_phase_on_a_2x2_cube():
    import jax
    r = chip_smoke.primitives_phase(jax.devices()[:4], payload=(4, 32))
    # 4 PE<->PE primitives x 3 selections x (auto + stages), and the
    # rooted four: every registered stage plus auto, at every selection
    from repro.core.comm import applicability
    table = applicability()
    per_sel = sum(1 + len(table[p]) for p in table)
    assert r["checks"] == 3 * per_sel


def test_sharded_step_phase_matches_one_device():
    import jax
    cfg = _tiny(2, n_heads=8, n_kv_heads=4, tp=4)
    r = chip_smoke.sharded_step_phase(cfg, jax.devices()[:4], batch=1,
                                      seq=64, steps=2)
    assert r["sharded"]["cube"] == "Hypercube[data=1,tp=4; dcn=()]"
    assert r["sharded"]["param_devices"] == 4


def test_phases_import_no_fallback_or_host_device_setup():
    """Nothing the script's phases import may swap a kernel for its
    reference off TPU (the ``kernels/*/ops`` dispatchers), or set
    virtual-host-device flags at import (``launch/dryrun``, ``launch/perf``,
    ``benchmarks/_timing``)."""
    probe = ("import sys, chip_smoke; "
             "import repro.launch.train, repro.launch.serve, repro.serving, "
             "repro.data.pipeline, repro.models.lm, repro.models.serving, "
             "repro.core.comm, repro.core.hypercube, repro.testing.oracles, "
             "repro.testing.substrate; "
             "print(' '.join(sorted(sys.modules)))")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    loaded = set(p.stdout.split())
    assert "repro.launch.train" in loaded
    forbidden = {"repro.kernels.attention.ops", "repro.kernels.rwkv6.ops",
                 "repro.kernels.reorder.ops", "repro.launch.dryrun",
                 "repro.launch.perf", "benchmarks._timing"}
    assert not loaded & forbidden, loaded & forbidden


_PROBE = ("from repro.launch.cache import use_compile_cache; "
          "print(use_compile_cache()); import jax; "
          "print(jax.config.jax_compilation_cache_dir); "
          "jax.jit(lambda x: x * 2)(1.0).block_until_ready()")


def _probe(env_extra, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               **env_extra)
    p = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, timeout=300, env=env, cwd=tmp_path)
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.split()


def test_compile_cache_follows_the_environment(tmp_path):
    where = tmp_path / "cache"
    said, configured = _probe({"JAX_COMPILATION_CACHE_DIR": str(where)},
                              tmp_path)
    assert said == configured == str(where)
    assert any(where.iterdir()), "nothing cached in JAX_COMPILATION_CACHE_DIR"


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    said, configured = _probe({"JAX_ENABLE_COMPILATION_CACHE": "false"},
                              tmp_path)
    assert said == configured == os.path.join(ROOT, ".jax_cache")
