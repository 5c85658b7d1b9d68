"""ShardingRules: logical-axis mapping, divisibility safety, FSDP/seq modes."""

import warnings

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import sharding as sh

jax.config.update("jax_platform_name", "cpu")


def fake_mesh(shape, names):
    class M:
        pass

    m = M()
    m.shape = dict(zip(names, shape))
    m.axis_names = names
    return m


class TestRules:
    def test_default_tp_mapping(self):
        r = sh.ShardingRules()
        assert r.spec((sh.VOCAB, sh.D_MODEL)) == P("model", None)
        assert r.spec((sh.D_MODEL, sh.HEADS)) == P(None, "model")
        assert r.spec((sh.BATCH, None, None)) == P("data", None, None)

    def test_multipod_batch_axes(self):
        r = sh.ShardingRules(batch_axes=("pod", "data"))
        assert r.spec((sh.BATCH, None)) == P(("pod", "data"), None)

    def test_axis_used_once(self):
        r = sh.ShardingRules()
        # two model-mapped logical axes: second one must drop
        assert r.spec((sh.HEADS, sh.KV_HEADS)) == P("model", None)

    def test_fsdp_axes(self):
        r = sh.ShardingRules(fsdp_axes=(sh.D_MODEL,))
        assert r.spec((sh.D_MODEL, sh.FF)) == P(("data",), "model")

    def test_seq_shard_mode(self):
        r = sh.ShardingRules(seq_shard=True)
        # long-context: KV seq over data, batch replicated
        assert r.spec((sh.LAYERS, sh.BATCH, sh.SEQ, sh.KV_HEADS, None)) == P(
            None, None, ("data",), "model", None
        )

    def test_seq_unsharded_by_default(self):
        r = sh.ShardingRules()
        assert r.spec((sh.BATCH, sh.SEQ, None)) == P("data", None, None)


class TestDivisibilitySafety:
    def test_drops_nondividing_axis(self):
        mesh = fake_mesh((16, 16), ("data", "model"))
        r = sh.ShardingRules()
        # kv_heads=1 cannot shard over model=16
        spec = r.spec_for_shape(mesh, (sh.LAYERS, sh.BATCH, sh.SEQ, sh.KV_HEADS, None),
                                (4, 128, 32768, 1, 256))
        assert spec == P(None, "data", None, None, None)

    def test_keeps_dividing_axis(self):
        mesh = fake_mesh((16, 16), ("data", "model"))
        r = sh.ShardingRules()
        spec = r.spec_for_shape(mesh, (sh.D_MODEL, sh.HEADS), (4096, 8192))
        assert spec == P(None, "model")

    def test_batch_of_one_replicates(self):
        mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
        r = sh.ShardingRules(batch_axes=("pod", "data"))
        spec = r.spec_for_shape(mesh, (sh.BATCH, None), (1, 1))
        assert spec == P(None, None)

    def test_tuple_axis_product(self):
        mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
        r = sh.ShardingRules(batch_axes=("pod", "data"))
        # batch 64 divisible by 32 (pod*data)
        assert r.spec_for_shape(mesh, (sh.BATCH, None), (64, 8)) == P(
            ("pod", "data"), None
        )
        # batch 16 NOT divisible by 32
        assert r.spec_for_shape(mesh, (sh.BATCH, None), (16, 8)) == P(None, None)


class TestFallbackWarning:
    """Silent-replication fallback must not stay silent: a real size
    mismatch warns ShardingFallbackWarning; legitimate no-op cases
    (dim 1, duplicate mesh axis) stay quiet."""

    def test_warns_on_nondividing_dim(self):
        mesh = fake_mesh((16, 16), ("data", "model"))
        r = sh.ShardingRules()
        with pytest.warns(sh.ShardingFallbackWarning,
                          match="kv_heads.*dim 6.*not.*divisible"):
            spec = r.spec_for_shape(mesh, (sh.KV_HEADS, None), (6, 64))
        assert spec == P(None, None)  # behaviour unchanged: replicated

    def test_warns_on_nondividing_tuple_axis(self):
        mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
        r = sh.ShardingRules(batch_axes=("pod", "data"))
        with pytest.warns(sh.ShardingFallbackWarning, match="size 32"):
            r.spec_for_shape(mesh, (sh.BATCH, None), (16, 8))

    def test_silent_on_dim_one(self):
        # dim 1 = "nothing to shard" (B=1 chunks, squeezed axes) — not a
        # misconfiguration, must not spam
        mesh = fake_mesh((16, 16), ("data", "model"))
        r = sh.ShardingRules()
        with warnings.catch_warnings():
            warnings.simplefilter("error", sh.ShardingFallbackWarning)
            spec = r.spec_for_shape(mesh, (sh.KV_HEADS, sh.BATCH), (1, 32))
        assert spec == P(None, "data")

    def test_silent_on_duplicate_axis(self):
        # a later logical dim losing "model" to an earlier one is the
        # documented at-most-once rule, not a fallback
        mesh = fake_mesh((16, 16), ("data", "model"))
        r = sh.ShardingRules()
        with warnings.catch_warnings():
            warnings.simplefilter("error", sh.ShardingFallbackWarning)
            spec = r.spec_for_shape(mesh, (sh.HEADS, sh.KV_HEADS), (32, 32))
        assert spec == P("model", None)


class TestRulesForMesh:
    def test_detects_pod_axis(self):
        devs = np.asarray(jax.devices()[:1])
        mesh = Mesh(devs.reshape(1, 1, 1), ("pod", "data", "model"))
        r = sh.rules_for_mesh(mesh)
        assert r.batch_axes == ("pod", "data")
        mesh2 = Mesh(devs.reshape(1, 1), ("data", "model"))
        r2 = sh.rules_for_mesh(mesh2)
        assert r2.batch_axes == ("data",)


class TestAmbientMesh:
    def test_none_outside_any_mesh_context(self):
        assert sh.ambient_mesh() is None

    def test_ambient_mesh_is_discovered(self):
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        with mesh:
            found = sh.ambient_mesh()
        assert found is not None
        assert "data" in found.axis_names

    def test_constrain_is_noop_without_mesh(self):
        import jax.numpy as jnp

        x = jnp.ones((4, 8))
        out = sh.constrain(x, sh.ShardingRules(), (sh.BATCH, None))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    def test_constrain_inside_jit_under_ambient_mesh(self):
        import jax.numpy as jnp

        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        rules = sh.ShardingRules()

        @jax.jit
        def f(x):
            return sh.constrain(x, rules, (sh.BATCH, None)) * 2.0

        with mesh:
            out = f(jnp.ones((4, 8)))
        np.testing.assert_array_equal(np.asarray(out), np.full((4, 8), 2.0))
