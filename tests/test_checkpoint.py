"""Elastic checkpointing: atomic layout, async save, reshard-on-restore.

Covers the redesigned topology-bound :class:`CheckpointManager` surface —
``save(step, TrainState)`` / ``restore(step)`` / ``restore_params(step,
serve_topo=...)`` — the deprecated positional shims, the manifest's
structural fingerprint validation, crash/GC hardening, async write-error
propagation, save/train overlap (asserted via spans), reshard-on-restore
bit-identity against both the pure-NumPy placement oracle and direct init
on the target topology, and the torch-free Hugging Face import path.
"""
import os
import shutil
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import hf_import, layout, reshard
from repro.checkpoint.manager import CheckpointManager, TrainState
from repro.configs import get
from repro.core import program
from repro.core.comm import CommTrace
from repro.launch.mesh import make_mesh
from repro.models.params import init_params, param_specs
from repro.models.topology import build_serve_topology, build_topology
from repro.testing import oracles
from repro import telemetry


def _tiny_state(seed=0):
    rng = np.random.default_rng(seed)
    params = {"w": rng.standard_normal((4, 8)).astype(np.float32),
              "b": {"scale": rng.standard_normal(8).astype(np.float32)}}
    opt = {"m": jax.tree.map(np.zeros_like, params),
           "count": np.int32(3)}
    return TrainState(params=jax.tree.map(jnp.asarray, params),
                      opt=jax.tree.map(jnp.asarray, opt))


def _assert_tree_equal(a, b):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(la) == len(lb)
    for (pa, va), (pb, vb) in zip(la, lb):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))


# --------------------------------------------------------------- layout
def test_all_steps_ignores_foreign_entries(tmp_path):
    root = str(tmp_path)
    mgr = CheckpointManager(root, async_save=False)
    mgr.save(10, _tiny_state())
    mgr.save(20, _tiny_state())
    # foreign debris a hardened all_steps must skip
    os.makedirs(os.path.join(root, "step_00000030.tmp"))  # killed writer
    os.makedirs(os.path.join(root, "notastep"))
    open(os.path.join(root, "step_00000040"), "w").close()  # file, not dir
    open(os.path.join(root, "events.log"), "w").close()
    os.makedirs(os.path.join(root, "step_123"))  # wrong digit count
    assert mgr.all_steps() == [10, 20]
    assert mgr.latest_step() == 20


def test_killed_mid_write_is_invisible_and_retry_wins(tmp_path):
    root = str(tmp_path)
    mgr = CheckpointManager(root, async_save=False)
    # simulate a writer killed mid-step-5: partial .tmp with garbage files
    debris = os.path.join(root, "step_00000005.tmp")
    os.makedirs(debris)
    np.save(os.path.join(debris, "arr_0.npy"), np.zeros(3))
    open(os.path.join(debris, "garbage"), "w").close()

    assert mgr.all_steps() == []
    with pytest.raises(FileNotFoundError, match="no checkpoint for step 5"):
        mgr.restore(5)

    state = _tiny_state(seed=7)
    mgr.save(5, state)  # retry overwrites the debris
    assert mgr.all_steps() == [5]
    assert not os.path.exists(debris)
    restored = mgr.restore(5)
    _assert_tree_equal(restored.params, state.params)
    _assert_tree_equal(restored.opt, state.opt)


def test_keep_last_gc_and_in_flight_protection(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False, keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tiny_state(seed=s))
    assert mgr.all_steps() == [3, 4]

    # a step registered as in-flight is never collected, even when the GC
    # horizon would otherwise claim it
    mgr.keep_last = 1
    mgr._writing.add(3)
    mgr._gc()
    assert mgr.all_steps() == [3, 4]
    mgr._writing.discard(3)
    mgr._gc()
    assert mgr.all_steps() == [4]


# ----------------------------------------------------------- async save
def test_async_write_error_surfaces_at_wait(tmp_path, monkeypatch):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    orig_save = np.save

    def failing_save(path, arr, *a, **k):
        raise OSError("disk full (simulated)")

    monkeypatch.setattr(np, "save", failing_save)
    mgr.save(1, _tiny_state())
    with pytest.raises(OSError, match="disk full"):
        mgr.wait()
    # the failed step never became visible, and the manager recovers
    assert mgr.all_steps() == []
    monkeypatch.setattr(np, "save", orig_save)
    mgr.save(2, _tiny_state())
    mgr.wait()
    assert mgr.all_steps() == [2]


def test_async_write_error_surfaces_at_next_save(tmp_path, monkeypatch):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    orig_save = np.save
    monkeypatch.setattr(
        np, "save",
        lambda *a, **k: (_ for _ in ()).throw(OSError("bad sector")))
    mgr.save(1, _tiny_state())
    monkeypatch.setattr(np, "save", orig_save)
    with pytest.raises(OSError, match="bad sector"):
        mgr.save(2, _tiny_state())
    mgr.save(3, _tiny_state())
    mgr.wait()
    assert mgr.all_steps() == [3]


def test_async_save_overlaps_and_spans_cross_threads(tmp_path, monkeypatch):
    """save() returns after the host gather; the writes land on the
    executor.  Proven via spans: the worker's ``checkpoint.write.params`` span
    lives on its own tracer lane and extends past the save() dispatch."""
    state = _tiny_state()
    orig_save = np.save

    def slow_save(path, arr, *a, **k):
        time.sleep(0.03)
        return orig_save(path, arr, *a, **k)

    monkeypatch.setattr(np, "save", slow_save)
    # the two sections write concurrently (max_workers=2): the wall floor
    # is the slowest section, not the sum
    slowest = 0.03 * max(len(jax.tree.leaves(state.params)),
                         len(jax.tree.leaves(state.opt)))
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    with telemetry.Tracer() as tr:
        t0 = time.monotonic()
        mgr.save(1, state)
        dispatch = time.monotonic() - t0
        mgr.wait()
        durable = time.monotonic() - t0
    # dispatch did not pay for the writes
    assert dispatch < slowest <= durable

    spans = {sp.name: sp for sp in tr.finished()}
    main_tid = spans["checkpoint.gather.params"].tid
    assert spans["checkpoint.write.params"].tid != main_tid  # worker lane
    assert spans["checkpoint.write.opt"].tid != main_tid
    assert any(sp.name == "checkpoint.durable" and sp.ph == "i"
               for sp in tr.finished())
    assert mgr.all_steps() == [1]


def test_trainer_step_does_not_block_on_write(tmp_path, monkeypatch):
    """End-to-end overlap: with slowed disk writes, the train step after a
    checkpoint dispatch finishes before the checkpoint becomes durable."""
    from repro.data.pipeline import DataConfig, TokenStream
    from repro.optim import adamw
    from repro.runtime.trainer import Trainer, TrainConfig

    cfg = get("qwen3-1.7b").scaled_for_smoke()
    mesh = make_mesh((1, 1), ("data", "model"))
    topo = build_topology(cfg, mesh)
    tc = TrainConfig(warmup=2, lr=1e-3)
    params = init_params(cfg, topo, seed=0)
    opt = adamw.init_state(params, tc.adamw)
    n_leaves = len(jax.tree.leaves({"opt": opt, "params": params}))

    orig_save = np.save
    delay = 0.02

    def slow_save(path, arr, *a, **k):
        time.sleep(delay)
        return orig_save(path, arr, *a, **k)

    monkeypatch.setattr(np, "save", slow_save)
    stream = TokenStream(cfg, DataConfig(seq_len=32, global_batch=2,
                                         vocab_size=cfg.vocab_size))
    batches = ({k: jnp.asarray(v)
                for k, v in stream.global_batch_at(s).items()}
               for s in range(3))
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    with telemetry.Tracer() as tr:
        trainer = Trainer(cfg, topo, tc, checkpointer=mgr)
        trainer.run(params, opt, batches, checkpoint_every=2,
                    log_every=0, log=lambda *_: None)
        mgr.wait()

    steps = [sp for sp in tr.finished() if sp.name == "train.step"]
    durable = [sp for sp in tr.finished() if sp.name == "checkpoint.durable"]
    assert len(steps) == 3 and durable
    # the write takes at least n_leaves * delay; the step that ran behind
    # it finished long before the durable instant
    after = steps[2]
    assert after.ts + after.dur < durable[0].ts
    assert after.dur / 1e6 < n_leaves * delay
    assert mgr.all_steps() == [2]


# ------------------------------------------------- API redesign + shims
def test_deprecated_shims_match_new_surface(tmp_path):
    state = _tiny_state(seed=3)
    new_root, old_root = str(tmp_path / "new"), str(tmp_path / "old")
    new_mgr = CheckpointManager(new_root, async_save=False)
    new_mgr.save(7, state)

    old_mgr = CheckpointManager(old_root, async_save=False)
    with pytest.warns(DeprecationWarning, match="save\\(step, params"):
        old_mgr.save(7, state.params, state.opt)

    # identical bytes on disk (manifest + every leaf file)
    for d in (new_root, old_root):
        assert layout.list_steps(d) == [7]
    m_new = layout.read_manifest(layout.step_dir(new_root, 7))
    m_old = layout.read_manifest(layout.step_dir(old_root, 7))
    assert m_new == m_old
    assert m_new["fingerprint"] == layout.fingerprint(m_new["leaves"])

    st = new_mgr.restore(7)
    with pytest.warns(DeprecationWarning, match="restore\\(step\\)"):
        params, opt = old_mgr.restore(7, state.params, state.opt)
    _assert_tree_equal(st.params, params)
    _assert_tree_equal(st.opt, opt)

    p_new = new_mgr.restore_params(7)
    with pytest.warns(DeprecationWarning, match="restore_params"):
        p_old = old_mgr.restore_params(7, state.params)
    _assert_tree_equal(p_new, p_old)
    _assert_tree_equal(p_new, state.params)


def test_fingerprint_validation_catches_architecture_mismatch(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    state = _tiny_state()
    mgr.save(1, state)

    # wrong leaf count
    bad_count = TrainState(params={"w": np.zeros((4, 8), np.float32)},
                           opt=state.opt)
    with pytest.raises(ValueError, match="architecture mismatch"):
        with pytest.warns(DeprecationWarning):
            mgr.restore(1, bad_count.params, bad_count.opt)

    # right count, wrong shape: the per-leaf record diff fires
    bad_shape = jax.tree.map(np.asarray, state.params)
    bad_shape["w"] = np.zeros((5, 8), np.float32)
    with pytest.raises(ValueError, match="does not match the restore"):
        with pytest.warns(DeprecationWarning):
            mgr.restore_params(1, bad_shape)


def test_restore_without_specs_rebuilds_from_manifest(tmp_path):
    """A spec-free manager restores structure from the manifest's leaf
    records (the fix for the dead v1 ``treedef`` field)."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    state = _tiny_state(seed=11)
    mgr.save(3, state)
    st = CheckpointManager(str(tmp_path)).restore(3)
    _assert_tree_equal(st.params, state.params)
    _assert_tree_equal(st.opt, state.opt)
    p = CheckpointManager(str(tmp_path)).restore_params(3)
    _assert_tree_equal(p, state.params)


# ------------------------------------------------------ reshard-on-restore
def _logical_coords(cube):
    """device -> logical coords map via the cube's device grid."""
    grid = np.asarray(cube.mesh.devices).reshape(tuple(cube.dim_sizes))
    return {grid[c].id: c for c in np.ndindex(*grid.shape)}


def test_scatter_matches_numpy_oracle(cube_2x4):
    cube = cube_2x4
    x = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    spec = (cube.dim_names[0], cube.dim_names[1])
    [placed] = reshard.scatter_to_cube(cube, [x], [spec])
    np.testing.assert_array_equal(np.asarray(placed), x)
    want = oracles.reshard(x, cube.dim_sizes, cube.dim_names, spec)
    coords = _logical_coords(cube)
    for sh in placed.addressable_shards:
        np.testing.assert_array_equal(
            np.asarray(sh.data), want[coords[sh.device.id]])


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "gemma3-1b",
                                  "phi3-mini-3.8b"])
def test_elastic_restore_bit_identical_across_topologies(arch, tmp_path):
    """Save on the training topology, restore onto a different serve
    topology: one rooted-scatter CommProgram with program_id provenance,
    bit-identical to direct init on the target, shards matching the
    pure-NumPy placement oracle."""
    cfg = get(arch).scaled_for_smoke()
    mesh = make_mesh((4, 2), ("data", "model"))
    train_topo = build_topology(cfg, mesh)
    serve_topo = build_serve_topology(cfg, mesh)
    assert dict(zip(train_topo.cube.dim_names, train_topo.cube.dim_sizes)) \
        != dict(zip(serve_topo.cube.dim_names, serve_topo.cube.dim_sizes))

    params = init_params(cfg, train_topo, seed=0)
    mgr = CheckpointManager(
        str(tmp_path), async_save=False, topo=train_topo,
        specs={"params": param_specs(cfg, train_topo), "opt": None})
    mgr.save(1, TrainState(params=params))

    serve_specs = param_specs(cfg, serve_topo)
    with CommTrace() as tr:
        restored = mgr.restore_params(1, serve_topo=serve_topo,
                                      specs=serve_specs)
    assert any(e.program_id == "ckpt-restore-params" for e in tr.events)
    assert "ckpt-restore-params" in tr.summary()["programs"]

    direct = init_params(cfg, serve_topo, seed=0)
    _assert_tree_equal(restored, direct)

    # spot-check physical placement of one sharded leaf vs the oracle
    cube = serve_topo.cube
    coords = _logical_coords(cube)
    flat = jax.tree_util.tree_flatten_with_path(restored)[0]
    spec_flat = reshard.flatten_specs(serve_specs, [v for _, v in flat])
    checked = 0
    for (path, leaf), spec in zip(flat, spec_flat):
        if not any(s is not None for s in spec):
            continue
        want = oracles.reshard(np.asarray(leaf), cube.dim_sizes,
                               cube.dim_names, spec)
        for sh in leaf.addressable_shards:
            np.testing.assert_array_equal(
                np.asarray(sh.data), want[coords[sh.device.id]])
        checked += 1
        if checked >= 2:
            break
    assert checked


def test_save_gather_program_hits_lower_cache(tmp_path, cube_2x2x2):
    """The save-side gather program's structural fingerprint is
    step-invariant, so the second save reuses the lowered program."""
    cube = cube_2x2x2
    specs = {"a": P("a", ("b", "c")), "b": P(("a", "b"), None)}
    rng = np.random.default_rng(0)
    trees = [{"a": jnp.asarray(rng.standard_normal((8, 8),).astype("f4")),
              "b": jnp.asarray(rng.standard_normal((8, 4)).astype("f4"))}
             for _ in range(2)]
    placed = [jax.tree.unflatten(
        jax.tree.structure(t),
        reshard.scatter_to_cube(cube, jax.tree.leaves(t),
                                reshard.flatten_specs(specs,
                                                      jax.tree.leaves(t))))
        for t in trees]
    mgr = CheckpointManager(str(tmp_path), async_save=False, topo=cube,
                            specs={"params": specs, "opt": None})
    base = dict(program.LOWER_STATS)
    mgr.save(1, TrainState(params=placed[0]))
    mgr.save(2, TrainState(params=placed[1]))
    assert program.LOWER_STATS["cache_hits"] >= base.get("cache_hits", 0) + 1
    st1 = mgr.restore_params(1)
    _assert_tree_equal(st1, trees[0])


# --------------------------------------------------------------- HF import
def test_hf_roundtrip_qwen3(tmp_path):
    cfg = get("qwen3-1.7b").scaled_for_smoke()
    mesh = make_mesh((1, 1), ("data", "model"))
    topo = build_topology(cfg, mesh)
    params = jax.tree.map(np.asarray, init_params(cfg, topo, seed=0))

    sd = hf_import.export_state_dict(params, cfg)
    assert "lm_head.weight" in sd  # qwen3-1.7b does not tie embeddings
    st = str(tmp_path / "model.safetensors")
    pt = str(tmp_path / "pytorch_model.bin")
    hf_import.write_safetensors(st, sd)
    hf_import.write_pytorch_bin(pt, sd)
    for path in (st, pt):
        back = hf_import.import_state_dict(
            hf_import.read_state_dict(path), cfg, topo)
        _assert_tree_equal(params, back)


def test_hf_import_rejects_unmapped_keys(tmp_path):
    cfg = get("qwen3-1.7b").scaled_for_smoke()
    mesh = make_mesh((1, 1), ("data", "model"))
    topo = build_topology(cfg, mesh)
    params = jax.tree.map(np.asarray, init_params(cfg, topo, seed=0))
    sd = hf_import.export_state_dict(params, cfg)
    sd["model.layers.0.self_attn.rotary_emb.inv_freq"] = np.zeros(4)  # ok
    sd["model.layers.0.self_attn.q_proj.bias"] = np.zeros(4)  # not ok
    with pytest.raises(ValueError, match="no mapping"):
        hf_import.import_state_dict(sd, cfg, topo)
    tree = hf_import.import_state_dict(sd, cfg, topo, strict=False)
    _assert_tree_equal(params, tree)


def test_hf_import_unsupported_architectures():
    cfg = get("rwkv6-7b").scaled_for_smoke()
    with pytest.raises(NotImplementedError, match="no[\\s\\S]*mapping"):
        hf_import.import_state_dict({}, cfg)
