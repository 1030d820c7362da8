"""Data-pipeline determinism + checkpoint store semantics."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import store
from repro.data.pipeline import DataConfig, SyntheticLM


def _pipe(gb=8, seq=32, seed=7):
    return SyntheticLM(DataConfig(vocab_size=1000, seq_len=seq,
                                  global_batch=gb, seed=seed))


def test_restart_replay_exact():
    """The fault-tolerance property: batches at step s are identical across
    'restarts' (fresh pipeline objects)."""
    a, b = _pipe(), _pipe()
    for step in (0, 3, 17):
        np.testing.assert_array_equal(a.global_batch_at(step),
                                      b.global_batch_at(step))


@settings(max_examples=20, deadline=None)
@given(step=st.integers(0, 50), num_shards=st.sampled_from([1, 2, 4, 8]))
def test_shards_partition_global_batch(step, num_shards):
    p = _pipe()
    g = p.global_batch_at(step)
    parts = [p.shard_batch_at(step, s, num_shards) for s in range(num_shards)]
    np.testing.assert_array_equal(np.concatenate(parts), g)


def test_distinct_steps_distinct_data():
    p = _pipe()
    assert not np.array_equal(p.global_batch_at(0), p.global_batch_at(1))


def test_zipf_skew():
    p = _pipe(gb=32, seq=256)
    toks = p.global_batch_at(0).ravel()
    counts = np.bincount(toks, minlength=1000)
    # heavy head: the top token should be much more frequent than median
    assert counts.max() > 20 * max(np.median(counts), 1)


# -- checkpoint --------------------------------------------------------------


def _tree():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.bfloat16),
                  "d": jnp.asarray(3, jnp.int32)}}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    store.save(str(tmp_path), 5, t)
    restored, step = store.restore(str(tmp_path), t)
    assert step == 5
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), t, restored)


def test_latest_pointer_and_gc(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4):
        store.save(str(tmp_path), s, t)
    assert store.latest_step(str(tmp_path)) == 4
    store.gc(str(tmp_path), keep=2)
    dirs = sorted(os.listdir(str(tmp_path)))
    assert "step_3" in dirs and "step_4" in dirs and "step_1" not in dirs


def test_torn_write_never_visible(tmp_path):
    t = _tree()
    store.save(str(tmp_path), 1, t)
    # simulate a crashed writer: stray tmp dir must not be visible
    os.makedirs(os.path.join(str(tmp_path), ".tmp_step_9"))
    assert store.latest_step(str(tmp_path)) == 1
    _, step = store.restore(str(tmp_path), t)
    assert step == 1


def test_async_checkpointer(tmp_path):
    t = _tree()
    ck = store.AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (10, 20, 30):
        ck.submit(s, t)
    ck.close()
    assert store.latest_step(str(tmp_path)) == 30
    restored, _ = store.restore(str(tmp_path), t)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), t, restored)


def test_restore_with_shardings(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P
    t = _tree()
    store.save(str(tmp_path), 2, t)
    from repro.launch import mesh as mesh_mod
    mesh = mesh_mod.make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    restored, _ = store.restore(str(tmp_path), t, shardings=sh)
    assert restored["a"].sharding == NamedSharding(mesh, P())
