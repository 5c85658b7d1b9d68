"""Substrate tests: optimizer (fp32/int8), data pipeline determinism,
checkpoint roundtrip + elastic reshard, fault-tolerant loop, gradient
compression, train_step integration."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.data import Prefetcher, SyntheticLMDataset
from repro.distributed.compression import (
    compressed_psum_mean,
    make_compressed_dp_grad_fn,
)
from repro.models import model_zoo
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.runtime import Heartbeat, StragglerMonitor, run_resilient
from repro.training import make_train_step

jax.config.update("jax_platform_name", "cpu")


def quadratic_params(rng):
    return {
        "w": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32),
    }


class TestAdamW:
    @pytest.mark.parametrize("state_dtype", ["fp32", "int8"])
    def test_converges_on_quadratic(self, state_dtype):
        rng = np.random.default_rng(0)
        params = quadratic_params(rng)
        target = quadratic_params(np.random.default_rng(1))
        cfg = AdamWConfig(
            peak_lr=0.05, weight_decay=0.0, state_dtype=state_dtype,
            warmup_steps=5, decay_steps=300,
        )
        state = adamw_init(params, cfg)

        def loss_fn(p):
            return sum(
                jnp.sum(jnp.square(p[k] - target[k])) for k in p
            )

        @jax.jit
        def step(params, state):
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, state, _ = adamw_update(params, grads, state, cfg)
            return params, state, loss

        losses = []
        for _ in range(300):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        assert losses[-1] < 0.05 * losses[0], losses[-1]

    def test_int8_state_is_8bit(self):
        params = {"w": jnp.ones((16, 8), jnp.float32)}
        cfg = AdamWConfig(state_dtype="int8")
        st = adamw_init(params, cfg)
        assert st["m"]["w"]["q"].dtype == jnp.int8
        assert st["v"]["w"]["q"].dtype == jnp.int8

    def test_grad_clip_applied(self):
        params = {"w": jnp.zeros((4,), jnp.float32)}
        cfg = AdamWConfig(peak_lr=1.0, grad_clip=1e-3, warmup_steps=0,
                          weight_decay=0.0)
        st = adamw_init(params, cfg)
        huge = {"w": jnp.full((4,), 1e6, jnp.float32)}
        new, _, metrics = adamw_update(params, huge, st, cfg)
        assert float(metrics["grad_norm"]) > 1e5
        assert float(jnp.max(jnp.abs(new["w"]))) < 10.0


class TestDataPipeline:
    def test_deterministic_across_restarts(self):
        ds = SyntheticLMDataset(1000, 32, 4, seed=7)
        b1 = ds.batch(13)
        b2 = SyntheticLMDataset(1000, 32, 4, seed=7).batch(13)
        for k in b1:
            np.testing.assert_array_equal(b1[k], b2[k])

    def test_labels_are_next_tokens(self):
        ds = SyntheticLMDataset(1000, 16, 2, seed=0)
        b = ds.batch(0)
        assert b["tokens"].shape == (2, 16) and b["labels"].shape == (2, 16)

    def test_tokens_in_range_and_hot_ids(self):
        ds = SyntheticLMDataset(500, 256, 8, seed=1)
        b = ds.batch(3)
        assert b["tokens"].min() >= 0 and b["tokens"].max() < 500
        # zipf => some tokens repeat a lot
        _, counts = np.unique(b["tokens"], return_counts=True)
        assert counts.max() > 5

    def test_prefetcher_orders_steps(self):
        ds = SyntheticLMDataset(100, 8, 2, seed=2)
        pf = Prefetcher(ds, depth=2, start_step=5)
        s0, b0 = pf.next()
        s1, b1 = pf.next()
        pf.close()
        assert (s0, s1) == (5, 6)
        np.testing.assert_array_equal(b0["tokens"], ds.batch(5)["tokens"])


class TestCheckpointer:
    def test_roundtrip_and_retention(self, tmp_path):
        ckpt = Checkpointer(str(tmp_path), keep=2, async_save=False)
        state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
                 "opt": {"step": jnp.asarray(4, jnp.int32)}}
        for s in (1, 2, 3):
            ckpt.save(s, state, metadata={"note": "t"})
        assert ckpt.all_steps() == [2, 3]
        step, restored = ckpt.restore(state)
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]), np.asarray(state["params"]["w"])
        )

    def test_async_save_then_restore(self, tmp_path):
        ckpt = Checkpointer(str(tmp_path), async_save=True)
        state = {"w": jnp.ones((4, 4))}
        ckpt.save(10, state)
        ckpt.wait()
        step, restored = ckpt.restore(state)
        assert step == 10

    def test_elastic_reshard_on_restore(self, tmp_path):
        """Save unsharded, restore onto a different mesh sharding."""
        if jax.device_count() < 2:
            pytest.skip("needs >1 device")  # pragma: no cover
        ckpt = Checkpointer(str(tmp_path), async_save=False)
        state = {"w": jnp.arange(16.0).reshape(4, 4)}
        ckpt.save(1, state)
        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("data",))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        _, restored = ckpt.restore(state, shardings=sh)
        assert restored["w"].sharding == sh["w"]
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))


class TestFaultTolerance:
    def test_heartbeat_liveness(self, tmp_path):
        hb = Heartbeat(str(tmp_path / "hb.json"), interval_s=0.05)
        hb.start()
        import time

        time.sleep(0.15)
        hb.stop()
        assert Heartbeat.is_alive(str(tmp_path / "hb.json"), timeout_s=5.0)
        assert not Heartbeat.is_alive(str(tmp_path / "missing.json"), 1.0)

    def test_straggler_monitor_flags(self):
        mon = StragglerMonitor(threshold=2.0, min_steps=4)
        for i in range(8):
            assert not mon.record(i, 0.1)
        assert mon.record(8, 0.5)  # 5x median
        assert mon.flags == [8]

    def test_run_resilient_restores_and_replays(self, tmp_path):
        ckpt = Checkpointer(str(tmp_path), async_save=False)
        executed = []
        state = {"x": jnp.zeros(())}
        ckpt.save(0, state)
        fail_at = {3}

        def step_fn(step):
            if step in fail_at:
                fail_at.discard(step)  # fail once
                raise RuntimeError("simulated node failure")
            executed.append(step)
            ckpt.save(step + 1, {"x": jnp.asarray(float(step + 1))})

        def restore_fn():
            return ckpt.latest_step()

        failures = run_resilient(step_fn, 0, 6, restore_fn, max_failures=2)
        assert failures == 1
        assert executed == [0, 1, 2, 3, 4, 5]

    def test_run_resilient_gives_up(self, tmp_path):
        def step_fn(step):
            raise RuntimeError("permanent failure")

        with pytest.raises(RuntimeError):
            run_resilient(step_fn, 0, 3, lambda: 0, max_failures=2,
                          backoff_s=0.0)


class TestGradientCompression:
    def _mesh(self, n):
        if jax.device_count() < n:
            pytest.skip("needs forced host devices")  # pragma: no cover
        return Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("data",))

    def test_compressed_psum_close_to_exact(self):
        mesh = self._mesh(2)
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)

        f = jax.shard_map(
            lambda x: compressed_psum_mean({"g": x}, "data")["g"],
            mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        )
        out = np.asarray(f(g))
        want = np.broadcast_to(np.asarray(g).mean(0, keepdims=True) * 0 + np.asarray(g), g.shape)
        # each shard receives the mean of both shards
        mean = np.asarray(g).mean(axis=0)
        rel = np.abs(out - mean[None]).max() / (np.abs(mean).max() + 1e-9)
        assert rel < 0.05, rel

    def test_dp_grad_fn_matches_uncompressed(self):
        mesh = self._mesh(2)
        rng = np.random.default_rng(1)
        params = {"w": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
        batch = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)

        def loss_fn(p, x):
            return jnp.mean(jnp.square(x @ p["w"]))

        f = make_compressed_dp_grad_fn(loss_fn, mesh)
        loss_c, grads_c = f(params, batch)
        loss_e, grads_e = jax.value_and_grad(loss_fn)(params, batch)
        assert abs(float(loss_c) - float(loss_e)) < 1e-5
        rel = float(
            jnp.max(jnp.abs(grads_c["w"] - grads_e["w"]))
            / (jnp.max(jnp.abs(grads_e["w"])) + 1e-9)
        )
        assert rel < 0.05, rel


class TestTrainStepIntegration:
    def test_loss_decreases_on_tiny_model(self):
        cfg = get_config("deepseek-7b", smoke=True)
        params, _ = model_zoo.init(jax.random.key(0), cfg)
        opt_cfg = AdamWConfig(peak_lr=3e-3, warmup_steps=2, decay_steps=50,
                              weight_decay=0.0)
        from repro.distributed import sharding as sh

        step_fn = jax.jit(
            make_train_step(cfg, sh.ShardingRules(), opt_cfg,
                            fwd_kwargs=dict(block_q=16, block_k=16))
        )
        ds = SyntheticLMDataset(cfg.vocab_size, 16, 4, seed=0)
        state = {"params": params,
                 "opt": __import__("repro.optim", fromlist=["adamw_init"]).adamw_init(params, opt_cfg)}
        losses = []
        for i in range(8):
            b = {k: jnp.asarray(v) for k, v in ds.batch(i % 2).items()}
            state, metrics = step_fn(state, b)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0], losses

    def test_grad_accum_matches_full_batch(self):
        cfg = get_config("deepseek-7b", smoke=True)
        params, _ = model_zoo.init(jax.random.key(1), cfg)
        opt_cfg = AdamWConfig(peak_lr=1e-3, warmup_steps=0, weight_decay=0.0)
        from repro.distributed import sharding as sh
        from repro.optim import adamw_init

        ds = SyntheticLMDataset(cfg.vocab_size, 16, 8, seed=3)
        batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
        s1 = {"params": params, "opt": adamw_init(params, opt_cfg)}
        s2 = jax.tree.map(lambda x: x, s1)
        f1 = jax.jit(make_train_step(cfg, sh.ShardingRules(), opt_cfg,
                                     fwd_kwargs=dict(block_q=16, block_k=16)))
        f4 = jax.jit(make_train_step(cfg, sh.ShardingRules(), opt_cfg,
                                     fwd_kwargs=dict(block_q=16, block_k=16),
                                     grad_accum=4))
        s1, m1 = f1(s1, batch)
        s2, m2 = f4(s2, batch)
        w1 = s1["params"]["layers"]["attn"]["wq"]
        w2 = s2["params"]["layers"]["attn"]["wq"]
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=2e-4)
