"""Tests for optimizer, checkpointing (refinable-timestamp MVCC), and the
dynamic-graph pipeline."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import MVCheckpointStore
from repro.core.clock import Order, Stamp, compare
from repro.optim import AdamWConfig, adamw, make_train_step


def quad_loss(params, batch):
    err = params["w"] @ batch["x"] - batch["y"]
    loss = jnp.mean(jnp.square(err))
    return loss, {"loss": loss}


def make_batch(rng, d=4):
    x = rng.normal(size=(d, 8)).astype(np.float32)
    w_true = rng.normal(size=(d, d)).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(w_true @ x)}


class TestAdamW:
    def test_loss_decreases(self):
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)}
        batch = make_batch(rng)
        step = make_train_step(quad_loss,
                               AdamWConfig(lr=3e-2, warmup_steps=1,
                                           total_steps=200,
                                           weight_decay=0.0))
        opt = adamw.init(params)
        first = None
        for i in range(100):
            params, opt, m = step(params, opt, batch)
            if first is None:
                first = float(m["loss"])
        assert float(m["loss"]) < first * 0.1

    def test_clip_and_schedule(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          schedule="cosine")
        lr0 = float(adamw.schedule_lr(cfg, jnp.asarray(1)))
        lr_mid = float(adamw.schedule_lr(cfg, jnp.asarray(10)))
        lr_end = float(adamw.schedule_lr(cfg, jnp.asarray(100)))
        assert lr0 < lr_mid
        assert lr_end < 1e-3
        g = {"w": jnp.full((10,), 100.0)}
        clipped, norm = adamw.clip_by_global_norm(g, 1.0)
        assert float(adamw.global_norm(clipped)) <= 1.0 + 1e-5


class TestMVCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        store = MVCheckpointStore(str(tmp_path), n_writers=2, writer_id=0)
        params = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                  "b": {"x": jnp.ones((4,), jnp.bfloat16)}}
        st = store.save(params, step=5)
        got, info = store.restore(params)
        assert info.step == 5
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(params["w"]))
        assert got["b"]["x"].dtype == jnp.bfloat16

    def test_latest_orders_by_stamp(self, tmp_path):
        store = MVCheckpointStore(str(tmp_path), n_writers=1)
        p = {"w": jnp.zeros((2,))}
        store.save(p, step=1)
        store.save({"w": jnp.ones((2,))}, step=2)
        info = store.latest()
        assert info.step == 2

    def test_concurrent_writers_refined_consistently(self, tmp_path):
        """Two writers with concurrent stamps: the oracle decision is
        monotonic — latest() returns the same winner every time."""
        a = MVCheckpointStore(str(tmp_path), n_writers=2, writer_id=0)
        b = MVCheckpointStore(str(tmp_path), n_writers=2, writer_id=1)
        a.save({"w": jnp.zeros((2,))}, step=10)
        b.save({"w": jnp.ones((2,))}, step=11)
        reader = MVCheckpointStore(str(tmp_path), n_writers=2)
        first = reader.latest().path
        for _ in range(5):
            assert reader.latest().path == first

    def test_epoch_bump_orders_after(self, tmp_path):
        store = MVCheckpointStore(str(tmp_path), n_writers=1)
        s1 = store.save({"w": jnp.zeros((2,))}, step=50)
        store.bump_epoch()
        s2 = store.save({"w": jnp.ones((2,))}, step=10)   # lower step!
        assert compare(s1, s2) is Order.BEFORE
        assert store.latest().step == 10                  # stamp wins

    def test_gc_keeps_newest(self, tmp_path):
        store = MVCheckpointStore(str(tmp_path), n_writers=1, keep=2)
        for i in range(5):
            store.save({"w": jnp.full((2,), float(i))}, step=i)
        infos = store.list_checkpoints()
        assert len(infos) == 2
        assert store.latest().step == 4

    def test_torn_checkpoint_ignored(self, tmp_path):
        store = MVCheckpointStore(str(tmp_path), n_writers=1)
        store.save({"w": jnp.zeros((2,))}, step=1)
        os.makedirs(tmp_path / "v_e0_99", exist_ok=True)  # no MANIFEST
        assert store.latest().step == 1


class TestDynamicGraphPipeline:
    def test_snapshot_batches_under_mutation(self):
        from repro.core import Weaver, WeaverConfig
        from repro.data.pipeline import DynamicGraphPipeline
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=2, seed=1))
        tx = w.begin_tx()
        for i in range(8):
            tx.create_vertex(f"d{i}")
        for i in range(7):
            tx.create_edge(f"d{i}", f"d{i+1}")
        assert w.run_tx(tx).ok
        pipe = DynamicGraphPipeline(w, d_feat=4, n_classes=3,
                                    pad_nodes=32, pad_edges=64)
        def mutate(wv):
            tx = wv.begin_tx()
            tx.create_vertex(f"new{wv.sim.now}")
            assert wv.run_tx(tx).ok
        it = pipe.batches(mutate_between=mutate)
        b1 = next(it)
        b2 = next(it)
        assert b1["x"].shape == (32, 4)
        # the second snapshot saw the mutation (one more live node)
        assert b2["label_mask"].sum() == b1["label_mask"].sum() + 1
