"""A run whose timed path is broken underneath comes out not correct:
once for each fault a cell can have, at a size a test can hold, under
limits for that size (``conftest.TINY_LIMITS``) that its sound runs
meet."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_tiny, tiny

TRAIN = "qwen3-1.7b.train-2k"
COLL = "cube2x2.coll-bw"
SERVE = "qwen3-1.7b.serve-chat"


def test_sound_runs_are_correct():
    for name in (TRAIN, COLL, SERVE):
        res = run_tiny(tiny(name))
        assert res["correct"], (name, res["checks"])
        assert res["attempted"] > 0 and res["failed"] == 0


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.runtime import trainer
    orig = trainer.Trainer.run

    def stuck(self, params, opt, batches, **kw):
        copy = lambda t: jax.tree.map(jnp.copy, t)
        _, _, hist = orig(self, copy(params), copy(opt), batches, **kw)
        return params, opt, hist
    monkeypatch.setattr(trainer.Trainer, "run", stuck)
    res = run_tiny(tiny(TRAIN))
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0, abs=1e-4)


def test_train_half_of_the_batch_left_out(monkeypatch):
    from repro.runtime import trainer
    orig = trainer.Trainer.run

    def half(self, params, opt, batches, **kw):
        cut = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
               for b in batches]
        return orig(self, params, opt, cut, **kw)
    monkeypatch.setattr(trainer.Trainer, "run", half)
    assert not run_tiny(tiny(TRAIN))["correct"]


def _coll(monkeypatch, broken):
    cell = tiny(COLL)
    mod = cell.runner()
    monkeypatch.setattr(cell, "runner", lambda: mod)
    mod.CALL = broken(mod.program_call)
    return run_tiny(cell)


def test_coll_exchange_between_chips_left_out(monkeypatch):
    def local_only(call):
        def f(comm, prim, v, nd):
            if prim == "all_gather":          # right shape, own block only
                return jnp.concatenate([v] * comm.group_size, axis=nd)
            if prim == "reduce_scatter":
                return jnp.split(v, comm.group_size, axis=nd + 1)[0]
            return v
        return f
    res = _coll(monkeypatch, local_only)
    assert not res["correct"] and res["checks"]["wrong_outputs"]["value"] > 0


def test_coll_answer_altered_where_produced(monkeypatch):
    def altered(call):
        def f(comm, prim, v, nd):
            out = call(comm, prim, v, nd)
            return out.at[(0,) * out.ndim].add(1.0)
        return f
    res = _coll(monkeypatch, altered)
    assert not res["correct"] and res["checks"]["wrong_outputs"]["value"] > 0


def test_serve_token_altered_where_produced(monkeypatch):
    from repro.serving import engine
    orig = engine.ServeEngine._apply_meta

    def altered(self, sampled):
        sampled = np.asarray(sampled).copy()
        sampled[0] = (sampled[0] + 1) % self.cfg.vocab_size
        return orig(self, sampled)
    monkeypatch.setattr(engine.ServeEngine, "_apply_meta", altered)
    res = run_tiny(tiny(SERVE))
    assert not res["correct"], res["checks"]
