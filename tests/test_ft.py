"""Fault tolerance: compressed checkpoints (atomic, bounded-lossy), restore,
resume-determinism, heartbeat policy, elastic replanning."""
import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro import models
from repro.data import make_pipeline
from repro.ft import (
    CheckpointManager,
    CheckpointPolicy,
    Decision,
    HeartbeatMonitor,
    LeafPolicy,
    replan,
    validate_divisibility,
)
from repro.ft.elastic import best_mesh_shape
from repro.optim import AdamWConfig, init_state
from repro.parallel import ParallelPlan
from repro.train.step import init_train_state, make_train_step

PLAN = ParallelPlan()


def _state(seed=0):
    cfg = configs.get_smoke("qwen1.5-0.5b")
    opt = AdamWConfig()
    return cfg, opt, init_train_state(jax.random.PRNGKey(seed), cfg, PLAN, opt)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_checkpoint_roundtrip_lossless_params(tmp_path, dtype):
    cfg = dataclasses.replace(configs.get_smoke("qwen1.5-0.5b"), dtype=dtype)
    state = init_train_state(jax.random.PRNGKey(0), cfg, PLAN, AdamWConfig())
    mgr = CheckpointManager(tmp_path, use_async=False)
    mgr.save(7, state)
    template = jax.tree.map(np.asarray, state)
    restored, _ = mgr.restore(template)
    for a, b in zip(
        jax.tree.leaves(template["params"]), jax.tree.leaves(restored["params"])
    ):
        assert a.dtype == b.dtype == np.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_lossy_moments_bounded(tmp_path):
    cfg, opt, state = _state()
    # realistic smooth moments
    state["opt"]["m"] = jax.tree.map(
        lambda p: jnp.cumsum(
            jax.random.normal(jax.random.PRNGKey(0), p.shape), -1
        ).astype(jnp.float32)
        * 1e-3,
        state["params"],
    )
    mgr = CheckpointManager(tmp_path, use_async=False)
    manifest = mgr._write(1, jax.tree.map(np.asarray, state), {})
    restored, _ = mgr.restore(jax.tree.map(np.asarray, state))
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, state["opt"]["m"])),
        jax.tree.leaves(restored["opt"]["m"]),
    ):
        rng = float(a.max() - a.min())
        if a.size >= 1024 and rng > 0:
            assert np.abs(a - b).max() <= 1e-4 * rng * (1 + 1e-6)
        else:
            np.testing.assert_array_equal(a, b)
    assert manifest["ratio"] > 1.2  # compression actually happened


def test_checkpoint_async_and_gc(tmp_path):
    cfg, opt, state = _state()
    mgr = CheckpointManager(tmp_path, keep=2, use_async=True)
    for s in [1, 2, 3, 4]:
        mgr.save(s, state)
    mgr.wait()
    assert mgr.list_steps() == [3, 4]


def test_checkpoint_atomic_no_partial(tmp_path):
    cfg, opt, state = _state()
    mgr = CheckpointManager(tmp_path, use_async=False)
    mgr.save(1, state)
    # a leftover tmp dir from a "crashed" save must not affect restore
    (tmp_path / ".tmp_step_2").mkdir()
    (tmp_path / ".tmp_step_2" / "garbage.bin").write_bytes(b"xx")
    restored, _ = mgr.restore(jax.tree.map(np.asarray, state))
    assert mgr.list_steps() == [1]


def test_checkpoint_corruption_detected(tmp_path):
    cfg, opt, state = _state()
    mgr = CheckpointManager(tmp_path, use_async=False)
    mgr.save(1, state)
    d = tmp_path / "step_1"
    victim = next(p for p in d.glob("*.bin"))
    blob = bytearray(victim.read_bytes())
    if len(blob) > 10:
        blob[5] ^= 0xFF
    victim.write_bytes(bytes(blob))
    with pytest.raises(Exception):
        mgr.restore(jax.tree.map(np.asarray, state))


def test_train_resume_deterministic(tmp_path):
    """save at step k, restore, and the (k+1)th step matches bit-for-bit
    (lossless params + deterministic data pipeline)."""
    cfg = configs.get_smoke("qwen1.5-0.5b")
    opt = AdamWConfig(lr=1e-3)
    state = init_train_state(jax.random.PRNGKey(0), cfg, PLAN, opt)
    step = make_train_step(cfg, PLAN, opt)
    pipe = make_pipeline(cfg, seq=16, global_batch=2)
    policy = CheckpointPolicy(rules=(("", LeafPolicy("lossless")),))
    mgr = CheckpointManager(tmp_path, policy=policy, use_async=False)

    for k in range(2):
        state, _ = step(state, {k2: jnp.asarray(v) for k2, v in pipe.batch_at(k).items()})
    mgr.save(2, state)
    state_a, _ = step(state, {k2: jnp.asarray(v) for k2, v in pipe.batch_at(2).items()})

    template = jax.tree.map(np.asarray, state)
    restored, _ = mgr.restore(template)
    restored = jax.tree.map(jnp.asarray, restored)
    state_b, _ = step(restored, {k2: jnp.asarray(v) for k2, v in pipe.batch_at(2).items()})
    for a, b in zip(jax.tree.leaves(state_a["params"]), jax.tree.leaves(state_b["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_heartbeat_straggler_and_death():
    t = [0.0]
    mon = HeartbeatMonitor(
        ["h0", "h1", "h2"], timeout_s=10, straggler_factor=2.0, clock=lambda: t[0]
    )
    for step in range(6):
        t[0] += 1.0
        mon.beat("h0", 1.0)
        mon.beat("h1", 1.0)
        mon.beat("h2", 3.5)  # slow host
    dec = {d.host: d for d in mon.observe()}
    assert dec["h0"].kind == "ok" and dec["h2"].kind == "straggler"
    t[0] += 20.0
    mon.beat("h0", 1.0)
    mon.beat("h2", 3.5)
    dec = {d.host: d for d in mon.observe()}
    assert dec["h1"].kind == "dead"
    assert set(mon.survivors()) == {"h0", "h2"}


def test_elastic_replan_and_divisibility():
    assert best_mesh_shape(512, 16) == (32, 16)
    assert best_mesh_shape(256, 16) == (16, 16)
    assert best_mesh_shape(240, 16) == (15, 16)
    assert best_mesh_shape(12, 16) == (12, 1) or best_mesh_shape(12, 16)[0] * best_mesh_shape(12, 16)[1] <= 12
    cfg = configs.get("granite-3-8b")
    checks = validate_divisibility(cfg, PLAN)
    assert all(checks.values())
