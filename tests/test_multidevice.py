"""Multi-device behaviours (gradient compression, elastic reshard, dry-run
cell) — run in subprocesses with forced host devices, since the main test
session keeps the default single device per the repo contract."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 4, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestCompressedGradSync:
    def test_int8_allreduce_matches_exact(self):
        out = run_py(
            """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.distributed.compression import make_compressed_dp_grad_fn
mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("data",))
rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}
batch = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
loss_fn = lambda p, x: jnp.mean(jnp.square(x @ p["w"]))
lc, gc = make_compressed_dp_grad_fn(loss_fn, mesh)(params, batch)
le, ge = jax.value_and_grad(loss_fn)(params, batch)
rel = float(jnp.max(jnp.abs(gc["w"] - ge["w"])) / (jnp.max(jnp.abs(ge["w"])) + 1e-9))
assert abs(float(lc) - float(le)) < 1e-5, (lc, le)
assert rel < 0.05, rel
print("OK", rel)
"""
        )
        assert "OK" in out


class TestElasticReshard:
    def test_checkpoint_restores_onto_new_mesh(self, tmp_path):
        out = run_py(
            f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.checkpoint import Checkpointer
ck = Checkpointer({str(tmp_path)!r}, async_save=False)
# "save" under a 4-way sharding
mesh4 = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("data",))
w = jax.device_put(jnp.arange(32.0).reshape(8, 4),
                   NamedSharding(mesh4, P("data", None)))
ck.save(1, {{"w": w}})
# "restart" with only 2 devices (elastic downscale)
mesh2 = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("data",))
sh = {{"w": NamedSharding(mesh2, P("data", None))}}
_, restored = ck.restore({{"w": w}}, shardings=sh)
assert restored["w"].sharding == sh["w"]
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
print("OK")
"""
        )
        assert "OK" in out


class TestShardedPagedPools:
    """Sharded serving cache behaviours (heads-parallel KV pools on a
    ("data", "model") mesh) — subprocesses with 8 forced host devices."""

    def test_page_table_translation_head_sharded(self):
        """phys_table + paged_entry on a HEAD-SHARDED pool must read exactly
        the rows a host-side numpy translation of the page table picks."""
        out = run_py(
            """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.serving import kv_cache as kvc, sharded as shd

cfg = get_config("deepseek-7b", smoke=True)
lay = kvc.layout_for(cfg, 4, 48, kv_format="bf16", layout="paged", page_size=8)
rules = shd.rules_for(2, 4)
cache = shd.shard_cache(kvc.init_cache_arrays(cfg, lay), cfg, lay, rules)
rng = np.random.default_rng(0)
pool = {n: jnp.asarray(rng.normal(size=a.shape), a.dtype)
        for n, a in cache["global"].items()}
pool = shd.shard_cache({"global": pool, "page_table": cache["page_table"],
                        "pos": cache["pos"]}, cfg, lay, rules)["global"]
# a scrambled but valid table: every slot maps a random disjoint page set
perm = rng.permutation(lay.num_pages)[: 4 * lay.pages_per_slot]
table = np.asarray(perm, np.int32).reshape(4, lay.pages_per_slot)
pt = shd.replicated(table, rules)
phys = kvc.phys_table(pt, lay.page_size, lay.max_seq)
Hk = cfg.num_kv_heads
entry = jax.jit(lambda p, ph: kvc.paged_entry(p, 1, ph, heads=Hk))(pool, phys)
# host reference: logical position t of slot b lives in pool row
# table[b, t // page] * page + t % page
rows = (table[:, np.arange(lay.max_seq) // lay.page_size] * lay.page_size
        + np.arange(lay.max_seq) % lay.page_size)
np.testing.assert_array_equal(np.asarray(phys), rows)
for n in ("k", "v"):
    want = np.asarray(pool[n])[1][rows]          # (B, S, Hk*D) token rows
    want = want.reshape(want.shape[:2] + (Hk, -1))  # (B, S, Hk, D)
    got = np.moveaxis(np.asarray(entry[n]), 1, 2)  # back to (B, S, Hk, D)
    np.testing.assert_array_equal(got, want, err_msg=n)
print("OK")
""",
            devices=8,
        )
        assert "OK" in out

    def test_zero_pages_and_reset_slot_touch_every_shard(self):
        """zero_pages on a sharded pool and reset_slot on a sharded slot
        stack must zero the target rows on EVERY leaf of every shard and
        leave everything else bit-intact."""
        out = run_py(
            """
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.serving import kv_cache as kvc, sharded as shd

cfg = get_config("deepseek-7b", smoke=True)
rules = shd.rules_for(2, 4)
rng = np.random.default_rng(0)

# paged pool: zero pages {1, 5} through the constrained jitted path
lay = kvc.layout_for(cfg, 4, 48, kv_format="int8", layout="paged", page_size=8)
cache = kvc.init_cache_arrays(cfg, lay)
cache["global"] = {n: jnp.asarray(rng.normal(size=a.shape) + 1.0, jnp.float32)
                   .astype(a.dtype) if a.dtype != jnp.int8
                   else jnp.asarray(rng.integers(1, 100, a.shape), jnp.int8)
                   for n, a in cache["global"].items()}
cache = shd.shard_cache(cache, cfg, lay, rules)
specs = kvc.cache_specs(cfg, lay)["global"]
ids = jnp.asarray(np.asarray([1, 5] + [-1] * 6, np.int32))
zeroed = jax.jit(lambda s, i: kvc.constrain_cache(
    kvc.zero_pages(s, i, lay.page_size), specs, rules))(cache["global"], ids)
tok = np.concatenate([np.arange(8, 16), np.arange(40, 48)])
for n, a in zeroed.items():
    host, before = np.asarray(a), np.asarray(cache["global"][n])
    td = 1  # token dim of every pool leaf after the layer dim
    if n == "k_planes":
        td = 2
    sel = [slice(None)] * host.ndim
    sel[td] = tok
    assert not np.any(host[tuple(sel)]), n
    keep = np.ones(host.shape[td], bool); keep[tok] = False
    sel[td] = keep
    np.testing.assert_array_equal(host[tuple(sel)], before[tuple(sel)],
                                  err_msg=n)
    assert len(a.sharding.device_set) == 8, (n, a.sharding)

# slot stack: reset_slot(2) zeroes exactly row 2 of every stack leaf
lay_s = kvc.layout_for(cfg, 4, 48, kv_format="bf16", layout="slot")
cache_s = kvc.init_cache_arrays(cfg, lay_s)
cache_s["global"] = {n: jnp.asarray(rng.normal(size=a.shape) + 1.0, a.dtype)
                     for n, a in cache_s["global"].items()}
cache_s["pos"] = jnp.asarray([3, 4, 5, 6], jnp.int32)
cache_s = shd.shard_cache(cache_s, cfg, lay_s, rules)
reset = jax.jit(lambda c: kvc.constrain_cache(
    kvc.reset_slot(c, lay_s, 2), kvc.cache_specs(cfg, lay_s), rules))(cache_s)
for n, a in reset["global"].items():
    host, before = np.asarray(a), np.asarray(cache_s["global"][n])
    assert not np.any(host[:, 2]), n
    mask = np.ones(host.shape[1], bool); mask[2] = False
    np.testing.assert_array_equal(host[:, mask], before[:, mask], err_msg=n)
assert np.asarray(reset["pos"]).tolist() == [3, 4, 0, 6]
print("OK")
""",
            devices=8,
        )
        assert "OK" in out

    def test_prefix_adoption_refcounts_mesh_invariant(self):
        """The host allocator never sees the mesh: an identical shared-prefix
        trace must leave IDENTICAL page tables, refcounts, and allocation
        counters at mesh 1x1 and 2x4."""
        out = run_py(
            """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import MCBPOptions
from repro.models import model_zoo
from repro.serving import kv_cache as kvc, sharded as shd
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler

cfg = get_config("deepseek-7b", smoke=True)
cfg = dataclasses.replace(cfg, mcbp=MCBPOptions(bgpp_rounds=4, bgpp_keep_ratio=1.0))
params, _ = model_zoo.init(jax.random.key(0), cfg)
rng = np.random.default_rng(3)
prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
def reqs():
    return [Request(rid=i,
                    prompt=np.concatenate([prefix, rng.integers(
                        0, cfg.vocab_size, (3 + i,)).astype(np.int32)]),
                    max_new_tokens=3 + i, arrival_step=[0, 6, 6, 9][i])
            for i in range(4)]
rng_state = rng.bit_generator.state

def run(rules):
    global rng
    rng.bit_generator.state = rng_state
    lay = kvc.layout_for(cfg, 4, 48, kv_format="bf16", layout="paged",
                         page_size=8)
    kw = {} if rules is None else {"rules": rules}
    s = Scheduler(params, cfg, lay, chunk_budget=6, **kw)
    for r in reqs():
        s.submit(r)
    s.run(max_steps=500)
    assert len(s.finished) == 4
    s.pager.check()
    return s

a, b = run(None), run(shd.rules_for(2, 4))
assert a.prefix_hit_tokens == b.prefix_hit_tokens > 0
np.testing.assert_array_equal(a.pager.table, b.pager.table)
np.testing.assert_array_equal(a.pager.refcount, b.pager.refcount)
assert a.pager.alloc_count == b.pager.alloc_count
assert a.pager.peak_pages == b.pager.peak_pages
assert a.pager.pages_in_use == b.pager.pages_in_use == 0
print("OK", a.prefix_hit_tokens)
""",
            devices=8,
        )
        assert "OK" in out

    def test_bgpp_phase1_no_cross_model_collectives(self):
        """Structural: the shard_map-routed two-phase BGPP paged attend
        (phase-1 plane gathers + top-k + the phase-2 survivor gather)
        compiles to ZERO collectives on a 2x4 mesh — every step is local to
        its head shard; the decode layer's cross-shard hops are the
        all-reduce after the row-parallel wo outside it and the MLP's."""
        out = run_py(
            """
import dataclasses, re
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.configs.base import MCBPOptions
from repro.serving import engine, kv_cache as kvc, sharded as shd

cfg = get_config("deepseek-7b", smoke=True)
cfg = dataclasses.replace(cfg, mcbp=MCBPOptions(bgpp_rounds=4, bgpp_keep_ratio=0.5))
lay = kvc.layout_for(cfg, 4, 48, kv_format="bgpp", layout="paged", page_size=8)
rules = shd.rules_for(2, 4)
cache = shd.shard_cache(kvc.init_cache_arrays(cfg, lay), cfg, lay, rules)
rng = np.random.default_rng(0)
q = jax.device_put(
    jnp.asarray(rng.normal(size=(4, cfg.num_heads, cfg.head_dim)), jnp.float32),
    NamedSharding(rules.mesh, P("data", "model", None)))
pt = jax.device_put(kvc.identity_page_table(lay), NamedSharding(rules.mesh, P()))
valid = jax.device_put(jnp.ones((4, lay.max_seq), bool),
                       NamedSharding(rules.mesh, P("data", None)))

def attend(q, store, pt, valid):
    phys = kvc.phys_table(pt, lay.page_size, lay.max_seq)
    return engine._bgpp_paged_decode_attend_sharded(
        q, store, 0, phys, valid, cfg, lay, rules)

txt = jax.jit(attend).lower(q, cache["global"], pt, valid).compile().as_text()
hits = sorted(set(re.findall(
    r"all-reduce|all-gather|all-to-all|collective-permute", txt)))
assert not hits, hits
out = jax.jit(attend)(q, cache["global"], pt, valid)
assert out.shape == (4, cfg.num_heads, cfg.head_dim)
print("OK")
""",
            devices=8,
        )
        assert "OK" in out


class TestTensorParallelServing:
    """deepseek-7b smoke (4 q / 4 kv heads, d_ff 160: both divide 4) served
    tensor-parallel on a forced 4-device [1, 4] mesh, and the placement
    that puts 1/4 of every matrix on each device."""

    def test_serve_matches_plain_reference(self):
        """Chunked prefill, then decode through the int8 slot cache, on
        the [1, 4] mesh, teacher-forced, against the benchmark's plain
        float32 reference (bench/configs/dense_decoder.py) with the int8
        KV arithmetic, on its seeded weights.

        Tolerances: int8 KV rounds each q row, K/V row and weighted-
        probability row to steps of 1/127 of the row's largest value, so
        the float32 rounding change of the all-reduces (about 1e-6) can
        move one value across a step boundary and the row's logits by a
        step's share: 2.8e-3 measured at most, most rows within 3e-6.  The
        same program in bfloat16, one precision below the float32 smoke
        configuration, reads 0.079-0.128 at most and an rms error of
        0.018-0.019.  The limits sit near the geometric middles: 1.5e-2
        at most, 3e-3 rms per request."""
        out = run_py(
            f"""
import sys
sys.path.insert(0, {REPO!r})
import numpy as np, jax, jax.numpy as jnp
from bench import system
from bench.spec import CODE, load_module
from repro.configs import get_config
from repro.serving import kv_cache as kvc, sharded as shd
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler

ref = load_module(CODE / "configs" / "dense_decoder.py")
cfg = get_config("deepseek-7b", smoke=True)
w = ref.init_weights(system.seed_key(2 ** 31 + 11), system.weight_shapes(cfg))
conf = {{"hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": 1e-6, "rope_theta": cfg.rope_theta,
        "kv_format": "int8"}}
lay = kvc.layout_for(cfg, 4, 48, kv_format="int8", layout="slot")
rng = np.random.default_rng(0)
plan = [(rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32),
         rng.integers(0, cfg.vocab_size, 6).astype(np.int32), int(a))
        for n, a in zip(rng.integers(5, 21, 4), (0, 0, 2, 5))]
s = Scheduler(w, cfg, lay, shd.rules_for(1, 4), chunk_budget=8,
              record_logits=True)
for i, (p, f, a) in enumerate(plan):
    s.submit(Request(rid=i, prompt=p, max_new_tokens=len(f),
                     arrival_step=a, forced_tokens=f))
s.run(max_steps=500)
got = {{r.rid: np.stack(r.logit_rows) for r in s.finished}}
assert sorted(got) == [0, 1, 2, 3]
assert max(len(p) for p, _, _ in plan) > 8  # some prompt takes two chunks
ic = s.stats()["kv_read"]
assert ic["mesh"] == {{"data": 1, "model": 4}} and ic["interconnect_bytes"] > 0
for i, (p, f, _) in enumerate(plan):
    seq = np.concatenate([p, f[:-1]])
    want = np.asarray(ref.logits(w, ref.conf_items(conf),
                                 jnp.asarray(seq)))[len(p) - 1:]
    d = got[i] - want
    assert float(np.max(np.abs(d))) <= 1.5e-2, (i, float(np.max(np.abs(d))))
    assert float(np.sqrt(np.mean(d ** 2))) <= 3e-3, i
print("OK")
"""
        )
        assert "OK" in out

    def test_each_device_holds_a_quarter_of_every_matrix(self):
        out = run_py(
            """
import numpy as np, jax
from repro.configs import get_config
from repro.models import model_zoo
from repro.serving import sharded as shd

cfg = get_config("deepseek-7b", smoke=True)
params, specs = model_zoo.init(jax.random.key(0), cfg)
placed = shd.shard_params(params, specs, shd.rules_for(1, 4))
flat = jax.tree_util.tree_flatten_with_path(placed)[0]
split = {"wq": -1, "wk": -1, "wv": -1, "gate": -1, "up": -1, "wo": 1,
         "down": 1, "embed": 0, "lm_head": 1}
total = whole = 0
for path, x in flat:
    name = jax.tree_util.keystr(path)
    leaf = path[-1].key
    shard = x.sharding.shard_shape(x.shape)
    total += x.nbytes
    if "norm" in name:
        assert shard == x.shape, name  # norms replicated
        whole += x.nbytes
        continue
    ax = split[leaf]
    want = list(x.shape)
    want[ax] //= 4
    assert list(shard) == want, (name, shard, x.shape)
held = {d: 0 for d in jax.devices()[:4]}
for x in jax.tree.leaves(placed):
    for sh_ in x.addressable_shards:
        held[sh_.device] += sh_.data.nbytes
assert set(held.values()) == {(total - whole) // 4 + whole}, held
print("OK")
"""
        )
        assert "OK" in out

    def test_only_collectives_are_two_allreduces_per_layer(self):
        """The decode step and a prefill chunk on [1, 4]: the attend keeps
        every head on its device (no all-gather, no all-to-all), and each
        layer's only collectives are the float32 all-reduces named
        ``tp.attn_out`` and ``tp.mlp_out``; the one other is the
        vocabulary-sharded embedding lookup's exact combine."""
        out = run_py(
            r"""
import re
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import model_zoo
from repro.serving import engine, kv_cache as kvc, sharded as shd

cfg = get_config("deepseek-7b", smoke=True)
rules = shd.rules_for(1, 4)
lay = kvc.layout_for(cfg, 4, 48, kv_format="int8", layout="slot")
params, specs = model_zoo.init(jax.random.key(0), cfg)
params = shd.shard_params(params, specs, rules)
cache = shd.shard_cache(kvc.init_cache_arrays(cfg, lay), cfg, lay, rules)
i32 = lambda v: jnp.asarray(v, jnp.int32)
progs = {
    "serve_step": jax.jit(engine.make_serve_step(cfg, lay, rules)).lower(
        params, cache, jnp.zeros((4, 1), jnp.int32)),
    "prefill_chunk": jax.jit(engine.make_prefill_chunk(cfg, lay, rules))
    .lower(params, cache, jnp.zeros((1, 8), jnp.int32), i32(0), i32(0),
           i32(8)),
}
kinds = r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
for name, low in progs.items():
    ops = [l for l in low.compile().as_text().splitlines()
           if re.search(r"= \S+ " + kinds + r"(-start)?\(", l)]
    by = {}
    for l in ops:
        kind = re.search(kinds, l.split("=", 1)[1]).group(1)
        scope = re.search(r'op_name="([^"]*)"', l).group(1)
        tag = next((t for t in ("tp.attn_out", "tp.mlp_out") if t in scope),
                   "other")
        assert kind == "all-reduce", (name, l)
        if tag != "other":
            assert " f32[" in l, (name, l)
        by[tag] = by.get(tag, 0) + 1
    assert by.pop("tp.attn_out") == by.pop("tp.mlp_out") == cfg.num_layers, (
        name, by)
    assert by.get("other", 0) <= 1, (name, by)
print("OK")
"""
        )
        assert "OK" in out

    def test_1x1_specs_unchanged(self):
        """A "model" axis of size 1 splits nothing: on [1, 1], as on the
        data-only meshes [2, 1] and [4, 1], every weight is whole on every
        device — the placement every one-chip program compiled to — and
        every projection prices as unsplit (abstract meshes: shapes only)."""
        import jax
        from jax.sharding import AbstractMesh
        from repro.configs import get_config
        from repro.distributed import sharding as sh
        from repro.models import model_zoo
        from repro.serving import sharded as shd

        for arch in ("deepseek-7b", "phi4-mini-3.8b"):
            cfg = get_config(arch, smoke=True)
            shapes = jax.eval_shape(lambda k: model_zoo.init(k, cfg)[0],
                                    jax.random.key(0))
            specs = model_zoo.param_specs(cfg)
            for mesh in ((1, 1), (2, 1), (4, 1)):
                rules = sh.rules_for_mesh(AbstractMesh(mesh, ("data", "model")))
                placed = shd.param_shardings(shapes, specs, rules)
                for s_, x in zip(jax.tree.leaves(placed),
                                 jax.tree.leaves(shapes)):
                    assert s_.is_fully_replicated, (arch, mesh, s_)
                    assert s_.shard_shape(x.shape) == x.shape
                assert set(shd.projection_shards(
                    shapes, specs, mesh).values()) == {(1, 1)}, (arch, mesh)


class TestDryRunCell:
    """One real dry-run cell end-to-end (the cheapest arch×shape) — proves
    the 512-device lower+compile machinery from inside the test suite."""

    @pytest.mark.slow
    def test_gemma1b_decode_cell_compiles(self, tmp_path):
        out = run_py(
            f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch.dryrun import run_cell
r = run_cell("gemma3-1b", "decode_32k", out_dir={str(tmp_path)!r}, verbose=False)
assert r["status"] == "ok", r
assert r["device_flops"] > 0 and r["collective_bytes"] > 0
assert r["memory_analysis"]["fits_16gb"], r["memory_analysis"]
print("OK", r["bottleneck"], round(r["roofline_fraction"], 4))
""",
            devices=512,
            timeout=900,
        )
        assert "OK" in out
