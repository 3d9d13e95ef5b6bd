"""Optimizer, schedule, data pipeline, checkpointing, train loop."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.manager import CheckpointManager, save_checkpoint, restore_checkpoint
from repro.data.pipeline import FileDataset, Prefetcher, SyntheticDataset
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.schedule import cosine_schedule
from repro.train.loop import StepMonitor, TrainLoop


# ------------------------------ optimizer ----------------------------- #
def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params, cfg)
    grad_fn = jax.grad(lambda p: jnp.sum((p["w"] - 1.0) ** 2))
    for _ in range(200):
        params, state, _ = adamw_update(params, grad_fn(params), state, cfg)
    np.testing.assert_allclose(np.asarray(params["w"]), [1.0, 1.0], atol=1e-2)


def test_adamw_clipping():
    cfg = AdamWConfig(lr=0.0, clip_norm=1.0)
    params = {"w": jnp.zeros(3)}
    state = adamw_init(params, cfg)
    g = {"w": jnp.asarray([100.0, 0.0, 0.0])}
    _, _, m = adamw_update(params, g, state, cfg)
    assert float(m["grad_norm"]) == pytest.approx(100.0)


def test_adamw_bf16_states():
    cfg = AdamWConfig(state_dtype="bfloat16")
    params = {"w": jnp.zeros((4, 4), jnp.float32)}
    state = adamw_init(params, cfg)
    assert state["mu"]["w"].dtype == jnp.bfloat16
    p2, s2, _ = adamw_update(params, {"w": jnp.ones((4, 4))}, state, cfg)
    assert s2["nu"]["w"].dtype == jnp.bfloat16
    assert p2["w"].dtype == jnp.float32


def test_cosine_schedule():
    assert float(cosine_schedule(0, 1.0, 10, 100)) == 0.0
    assert float(cosine_schedule(10, 1.0, 10, 100)) == pytest.approx(1.0)
    assert float(cosine_schedule(100, 1.0, 10, 100)) == pytest.approx(0.1)


# ------------------------------ data ---------------------------------- #
def test_synthetic_deterministic_and_resumable():
    d1 = SyntheticDataset(1000, 16, 4, seed=7)
    it = iter(d1)
    first = [next(it) for _ in range(3)]
    d2 = SyntheticDataset(1000, 16, 4, seed=7)
    d2.load_state_dict({"step": 2})
    b = next(iter(d2))
    np.testing.assert_array_equal(b["tokens"], first[2]["tokens"])


def test_synthetic_host_sharding_differs():
    a = SyntheticDataset(1000, 16, 4, seed=0, host_id=0, num_hosts=2)
    b = SyntheticDataset(1000, 16, 4, seed=0, host_id=1, num_hosts=2)
    assert not np.array_equal(next(iter(a))["tokens"], next(iter(b))["tokens"])


def test_labels_shift():
    d = SyntheticDataset(1000, 16, 2, seed=1)
    b = next(iter(d))
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_file_dataset(tmp_path):
    toks = (np.arange(10_000) % 251).astype(np.uint16)
    p = tmp_path / "data.bin"
    toks.tofile(p)
    ds = FileDataset(str(p), seq_len=32, batch=4, seed=0)
    b1 = next(iter(ds))
    assert b1["tokens"].shape == (4, 32)
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    ds2 = FileDataset(str(p), seq_len=32, batch=4, seed=0)
    np.testing.assert_array_equal(next(iter(ds2))["tokens"], b1["tokens"])


def test_prefetcher():
    ds = SyntheticDataset(100, 8, 2, seed=0)
    pf = Prefetcher(iter(ds), depth=2)
    batches = [next(pf) for _ in range(5)]
    assert len(batches) == 5
    pf.close()


# ------------------------------ checkpoint ---------------------------- #
def _tree():
    return {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "nest": {"b": jnp.ones((2, 2), jnp.bfloat16), "c": jnp.int32(7)},
    }


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t, extra={"data": {"step": 5}})
    restored, step, extra = restore_checkpoint(str(tmp_path), t)
    assert step == 5 and extra["data"]["step"] == 5
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree())
    assert not [d for d in os.listdir(tmp_path) if d.startswith("tmp.")]


def test_checkpoint_manager_async_and_gc(tmp_path):
    m = CheckpointManager(str(tmp_path), keep_last_k=2)
    for s in (1, 2, 3, 4):
        m.save_async(s, _tree())
    m.wait()
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert steps == ["step_00000003", "step_00000004"]
    assert m.latest_step() == 4


def test_checkpoint_resharding_restore(tmp_path):
    """Elastic restore: load with explicit shardings for the current mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    t = {"w": jnp.arange(8.0)}
    save_checkpoint(str(tmp_path), 0, t)
    mesh = make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data"))}
    restored, _, _ = restore_checkpoint(str(tmp_path), t, shardings=sh)
    assert restored["w"].sharding == sh["w"]


# ------------------------------ train loop ---------------------------- #
def test_step_monitor_flags_straggler():
    mon = StepMonitor(window=16, threshold=3.0)
    for i in range(10):
        mon.record(i, 0.1)
    assert mon.record(10, 1.0)  # 10x median
    assert 10 in mon.flagged


def test_train_loop_preemption_resume(tmp_path):
    """Kill-and-restart resumes bit-exact (fault tolerance contract)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import init_params
    from repro.train.step import make_train_step

    cfg = get_config("llama3.2-3b", reduced=True)
    oc = AdamWConfig(lr=1e-3)
    step = jax.jit(make_train_step(cfg, oc))

    def make(dsseed=3):
        params = init_params(cfg, jax.random.PRNGKey(1))
        opt = adamw_init(params, oc)
        ds = SyntheticDataset(cfg.vocab_size, 16, 4, seed=dsseed)
        wrapped = lambda p, o, b, i: step(p, o, b, jnp.int32(i))
        loop = TrainLoop(wrapped, ds, ckpt_dir=str(tmp_path), ckpt_every=5)
        return params, opt, loop

    # run 10 steps straight
    p, o, loop = make()
    p10, o10, m10 = loop.run(p, o, 10, log_every=0)

    # "preempt" at 5: fresh process state, restore, run remaining 5
    import shutil

    shutil.rmtree(tmp_path)
    p, o, loop = make()
    p5, o5, _ = loop.run(p, o, 5, log_every=0)
    p2, o2, loop2 = make()
    p2, o2, resumed = loop2.maybe_restore(p2, o2)
    assert resumed and loop2.step == 5
    pr, orr, mr = loop2.run(p2, o2, 5, log_every=0)
    assert float(mr["loss"]) == pytest.approx(float(m10["loss"]), rel=1e-5)
