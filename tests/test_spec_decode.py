"""Unit + integration tests for bit-plane speculative decoding.

Covers the pieces under ``Scheduler._spec_round`` individually — knob
resolution (kwarg > env > config) and layout legality
(``repro.serving.spec_decode``), truncated-plane draft weights, the
cross-leaf token scrub (``kv_cache.zero_token_range``) — plus two
scheduler-level contracts:

  * ``forced_tokens`` teacher-forcing alone (no speculation) is
    bit-identical to free-running greedy decode fed the same tokens, on
    slot AND paged layouts — the verify chain's correctness rests on the
    forced path being a faithful replay channel;
  * speculative greedy decode is bit-identical to non-speculative greedy
    decode (the small deterministic version of the fuzz oracle's
    ``spec_decode`` axis in tests/test_serving_fuzz.py), with
    ``stats()["spec"]`` satisfying the accounting identities.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import apply_spec_decode_overrides, get_config
from repro.models import model_zoo
from repro.serving import kv_cache as kvc
from repro.serving import spec_decode as spd
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler

jax.config.update("jax_platform_name", "cpu")

_MODELS = {}


def _model(arch="phi4-mini-3.8b"):
    if arch not in _MODELS:
        cfg = get_config(arch, smoke=True)
        params, _ = model_zoo.init(jax.random.key(0), cfg)
        _MODELS[arch] = (cfg, params)
    return _MODELS[arch]


def _requests(cfg, n=3, seed=0, max_new=(3, 8)):
    rng = np.random.default_rng(seed)
    return [Request(
        rid=i,
        prompt=rng.integers(
            0, cfg.vocab_size, (int(rng.integers(4, 18)),)
        ).astype(np.int32),
        max_new_tokens=int(rng.integers(*max_new)),
        arrival_step=3 * i,
    ) for i in range(n)]


def _drive(sched, reqs, check_pager=True):
    for r in reqs:
        sched.submit(r)
    for _ in range(1000):
        if not sched.num_pending:
            break
        sched.step()
        if check_pager and sched.pager is not None:
            sched.pager.check()
    assert not sched.num_pending, "trace did not drain"
    return {r.rid: r for r in sched.finished}


# --------------------------------------------------------------------------
# knob resolution and layout legality
# --------------------------------------------------------------------------


class TestResolveValidate:
    CFG = get_config("phi4-mini-3.8b", smoke=True)

    def test_defaults_off(self, monkeypatch):
        monkeypatch.delenv(spd.ENV_ENABLE, raising=False)
        spec = spd.resolve(self.CFG)
        assert not spec.enabled and spec.source == "config"
        assert spec.gamma == 4 and spec.planes == 4

    def test_env_beats_config(self, monkeypatch):
        monkeypatch.setenv(spd.ENV_ENABLE, "on")
        monkeypatch.setenv(spd.ENV_GAMMA, "2")
        monkeypatch.setenv(spd.ENV_PLANES, "6")
        spec = spd.resolve(self.CFG)
        assert spec.enabled and spec.source == "env"
        assert spec.gamma == 2 and spec.planes == 6

    def test_kwarg_beats_env(self, monkeypatch):
        # oracles pin spec per run regardless of the CI matrix env
        monkeypatch.setenv(spd.ENV_ENABLE, "on")
        monkeypatch.setenv(spd.ENV_GAMMA, "2")
        spec = spd.resolve(self.CFG, enabled=False, gamma=3)
        assert not spec.enabled and spec.source == "kwarg"
        assert spec.gamma == 3

    def test_config_override_helper(self):
        cfg = apply_spec_decode_overrides(
            self.CFG, enabled=True, gamma=2, planes=5)
        assert cfg.mcbp.spec_decode and cfg.mcbp.draft_gamma == 2
        assert cfg.mcbp.draft_planes == 5
        assert spd.resolve(cfg).enabled
        # None keeps the incoming config values
        same = apply_spec_decode_overrides(cfg)
        assert same.mcbp == cfg.mcbp

    def test_bad_env_is_loud(self, monkeypatch):
        monkeypatch.setenv(spd.ENV_ENABLE, "maybe")
        with pytest.raises(ValueError, match="not a boolean"):
            spd.resolve(self.CFG)

    @pytest.mark.parametrize("kw", [{"gamma": 0}, {"planes": 0},
                                    {"planes": 9}])
    def test_knob_validation(self, kw):
        with pytest.raises(ValueError, match="draft_"):
            spd.resolve(self.CFG, **kw)

    def test_env_enable_soft_disables_on_local_layers(self, monkeypatch):
        # nightly-matrix semantics: env=on means "speculative where
        # supported" — ring stacks run, just without speculation
        monkeypatch.setenv(spd.ENV_ENABLE, "on")
        cfg, _ = _model("gemma3-4b")
        layout = kvc.layout_for(cfg, 2, 32, kv_format="bf16")
        assert layout.local_layers
        spec = spd.validate(cfg, layout, spd.resolve(cfg))
        assert not spec.enabled

    def test_explicit_enable_on_local_layers_raises(self):
        cfg, _ = _model("gemma3-4b")
        layout = kvc.layout_for(cfg, 2, 32, kv_format="bf16")
        with pytest.raises(ValueError, match="rollback-safe"):
            spd.validate(cfg, layout, spd.resolve(cfg, enabled=True))


# --------------------------------------------------------------------------
# truncated-plane draft weights
# --------------------------------------------------------------------------


class TestTruncatePlaneParams:
    def _params(self):
        rng = np.random.default_rng(0)
        return {
            "w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(8,)), jnp.bfloat16),
            "ids": jnp.arange(4, dtype=jnp.int32),
        }

    def test_planes_ge_7_is_identity(self):
        params = self._params()
        assert spd.truncate_plane_params(params, 7) is params
        assert spd.truncate_plane_params(params, 8) is params

    def test_structure_shapes_dtypes_preserved(self):
        params = self._params()
        out = spd.truncate_plane_params(params, 3)
        assert set(out) == set(params)
        for n in params:
            assert out[n].shape == params[n].shape, n
            assert out[n].dtype == params[n].dtype, n

    def test_int_leaves_untouched(self):
        params = self._params()
        out = spd.truncate_plane_params(params, 2)
        np.testing.assert_array_equal(np.asarray(out["ids"]),
                                      np.asarray(params["ids"]))

    def test_error_monotone_in_dropped_planes(self):
        params = self._params()
        w = np.asarray(params["w"])
        errs = [float(np.max(np.abs(
            np.asarray(spd.truncate_plane_params(params, p)["w"]) - w
        ))) for p in (1, 3, 6)]
        assert errs[0] >= errs[1] >= errs[2]
        assert errs[0] > 0  # one plane genuinely truncates
        # 6 of 7 magnitude bits: error bounded by the dropped LSB's weight
        scale = float(np.max(np.abs(w))) / 127.0
        assert errs[2] <= 2 * scale + scale  # quantization + 1 dropped bit

    def test_kept_values_are_plane_aligned(self):
        params = self._params()
        planes = 3
        out = np.asarray(spd.truncate_plane_params(params, planes)["w"])
        w = np.asarray(params["w"])
        scale = max(float(np.max(np.abs(w))), 1e-12) / 127.0
        q = np.abs(np.rint(out / scale)).astype(np.int64)
        # every surviving magnitude is a multiple of 2^(7-planes)
        assert np.all(q % (1 << (7 - planes)) == 0)


# --------------------------------------------------------------------------
# cross-leaf token scrub (the rollback's device half)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["bf16", "int8", "bgpp"])
class TestZeroTokenRange:
    def test_slot_layout_scrubs_every_leaf(self, fmt):
        cfg, _ = _model()
        layout = kvc.layout_for(cfg, 2, 32, kv_format=fmt)
        cache = kvc.init_cache_arrays(cfg, layout)
        filled = {n: jnp.full_like(a, 3) for n, a in cache["global"].items()}
        tpos = np.full((2, 3), kvc.OOB_INDEX, np.int32)
        tpos[0, :2] = [5, 9]
        tpos[1, 0] = 40  # >= max_seq: must drop, not wrap
        out = kvc.zero_token_range(dict(filled), jnp.asarray(tpos),
                                   max_seq=layout.max_seq)
        for n, a in out.items():
            arr = np.asarray(a)
            # slot stacks: token-row bodies (L, B, S, Hk*D), heads-major
            # scales and bgpp leaves (L, B, Hk, S, ...), k_planes carrying
            # an extra leading NBITS dim -> batch at bdim, tokens after it
            bdim = 2 if n == "k_planes" else 1
            tdim = bdim + (1 if kvc.token_major(out, n) else 2)
            tok = np.moveaxis(np.moveaxis(arr, bdim, 0),
                              tdim, 1)  # (B, S, ...)
            assert np.all(tok[0, [5, 9]] == 0), f"{n}: target rows survive"
            keep = np.delete(tok[0], [5, 9], axis=0)
            assert np.all(keep == 3), f"{n}: slot 0 overreach"
            assert np.all(tok[1] == 3), f"{n}: OOB scrub leaked into slot 1"

    def test_paged_layout_scrubs_through_the_table(self, fmt):
        cfg, _ = _model()
        layout = kvc.layout_for(cfg, 2, 32, kv_format=fmt, layout="paged",
                                page_size=8)
        cache = kvc.init_cache_arrays(cfg, layout)
        filled = {n: jnp.full_like(a, 3) for n, a in cache["global"].items()}
        table = np.full((2, layout.pages_per_slot), -1, np.int32)
        table[0, 0], table[0, 1] = 2, 0  # logical pages 0,1 -> phys 2,0
        tpos = np.full((2, 4), kvc.OOB_INDEX, np.int32)
        # token 5 -> phys row 2*8+5; token 9 -> phys row 0*8+1;
        # token 21 maps to an unmapped page (pid -1): must drop
        tpos[0, :3] = [5, 9, 21]
        out = kvc.zero_token_range(
            dict(filled), jnp.asarray(tpos), page_table=jnp.asarray(table),
            page_size=layout.page_size, max_seq=layout.max_seq)
        zeroed = {2 * 8 + 5, 0 * 8 + 1}
        for n, a in out.items():
            tok = np.moveaxis(np.asarray(a), kvc._tok_dim(n), 0)
            for row in range(tok.shape[0]):
                want = 0 if row in zeroed else 3
                assert np.all(tok[row] == want), f"{n}: phys row {row}"


# --------------------------------------------------------------------------
# satellite: forced_tokens teacher-forcing == free-running decode
# --------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["slot", "paged"])
class TestForcedTokensTeacherForcing:
    """``forced_tokens`` must be a faithful replay channel: forcing the
    exact tokens a free-running greedy run produced yields bit-identical
    logits on every step, across layouts.  The speculative verify chain
    picks tokens through this same ``_pick_token`` path, so this is the
    spec oracle's foundation."""

    def test_forced_matches_free_running(self, layout):
        cfg, params = _model()
        lay = kvc.layout_for(cfg, 2, 48, kv_format="bf16", layout=layout,
                             page_size=8)
        reqs = _requests(cfg, n=3, seed=5)
        free_sched = Scheduler(params, cfg, lay, chunk_budget=6,
                               record_logits=True)
        free = _drive(free_sched, [Request(
            rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
            arrival_step=r.arrival_step) for r in reqs])
        forced_sched = Scheduler(params, cfg, lay, chunk_budget=6,
                                 record_logits=True,
                                 shared_fns=free_sched.shared_fns())
        forced = _drive(forced_sched, [Request(
            rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
            arrival_step=r.arrival_step,
            forced_tokens=np.asarray(free[r.rid].generated, np.int32),
        ) for r in reqs])
        for rid in free:
            assert forced[rid].generated == free[rid].generated
            assert len(forced[rid].logit_rows) == len(free[rid].logit_rows)
            for t, (a, b) in enumerate(zip(forced[rid].logit_rows,
                                           free[rid].logit_rows)):
                assert np.array_equal(a, b), (layout, rid, t)


# --------------------------------------------------------------------------
# scheduler-level speculative decode
# --------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["slot", "paged"])
class TestSpecSchedulerEquivalence:
    def _baseline(self, cfg, params, lay, reqs):
        sched = Scheduler(params, cfg, lay, chunk_budget=6,
                          record_logits=True, spec_decode=False)
        return sched, _drive(sched, [Request(
            rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
            arrival_step=r.arrival_step) for r in reqs])

    def test_spec_bit_identical_and_leak_free(self, layout):
        cfg, params = _model()
        lay = kvc.layout_for(cfg, 2, 48, kv_format="bf16", layout=layout,
                             page_size=8)
        reqs = _requests(cfg, n=3, seed=9)
        base_sched, want = self._baseline(cfg, params, lay, reqs)
        sched = Scheduler(params, cfg, lay, chunk_budget=6,
                          record_logits=True, spec_decode=True,
                          draft_gamma=2, draft_planes=4,
                          shared_fns=base_sched.shared_fns())
        got = _drive(sched, [Request(
            rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
            arrival_step=r.arrival_step) for r in reqs])
        for rid in want:
            assert got[rid].generated == want[rid].generated, (layout, rid)
            for t, (a, b) in enumerate(zip(got[rid].logit_rows,
                                           want[rid].logit_rows)):
                assert np.array_equal(a, b), (layout, rid, t)
        sp = sched.stats()["spec"]
        assert sp["rounds"] > 0 and sp["accepted_tokens"] > 0
        if sched.pager is not None:
            assert sched.pager.pages_in_use == 0

    def test_spec_stats_identities(self, layout):
        cfg, params = _model()
        lay = kvc.layout_for(cfg, 2, 48, kv_format="bf16", layout=layout,
                             page_size=8)
        sched = Scheduler(params, cfg, lay, chunk_budget=6,
                          spec_decode=True, draft_gamma=3, draft_planes=8)
        _drive(sched, _requests(cfg, n=3, seed=11))
        stats = sched.stats()
        sp, kv, wr = stats["spec"], stats["kv_read"], stats["weight_read"]
        assert sp["enabled"] and sp["gamma"] == 3
        # every decode-path token was produced by a verify step
        assert sp["accepted_tokens"] == stats["decoded_tokens"]
        assert kv["decode_steps"] == sp["draft_steps"] + sp["verify_steps"]
        # bytes/accepted-token == bytes/step / acceptance-rate, exactly
        np.testing.assert_allclose(
            kv["decode_bytes"] / sp["accepted_tokens"],
            kv["decode_bytes_per_step"]
            * kv["decode_steps"] / sp["accepted_tokens"])
        assert sp["kv_bytes_per_accepted_token"] == round(
            kv["decode_bytes"] / sp["accepted_tokens"])
        assert sp["weight_bytes_per_accepted_token"] == round(
            wr["decode_bytes"] / sp["accepted_tokens"])
        # planes=8 drafts with the REAL serve weights: greedy drafts are
        # perfect, so acceptance beats 1 token/round strictly
        assert sp["draft_source"] == "planes"
        assert sp["accepted_tokens_per_round"] > 1.0
        # per-request rows reconcile with the global counters
        fins = stats["requests"]
        assert sum(r["spec_accepted_tokens"] for r in fins) \
            == sp["accepted_tokens"]
        assert sum(r["spec_drafted_tokens"] for r in fins) \
            == sp["drafted_tokens"]


class TestSpecEnvPlumbing:
    def test_env_enables_whole_scheduler(self, monkeypatch):
        monkeypatch.setenv(spd.ENV_ENABLE, "on")
        monkeypatch.setenv(spd.ENV_GAMMA, "2")
        cfg, params = _model()
        lay = kvc.layout_for(cfg, 2, 48, kv_format="bf16")
        sched = Scheduler(params, cfg, lay, chunk_budget=6)
        assert sched.spec.enabled and sched.spec.gamma == 2
        # explicit kwarg still wins over the env (alone-run pinning)
        pinned = Scheduler(params, cfg, lay, chunk_budget=6,
                           spec_decode=False,
                           shared_fns=sched.shared_fns())
        assert not pinned.spec.enabled

    def test_env_on_ring_stack_runs_without_speculation(self, monkeypatch):
        monkeypatch.setenv(spd.ENV_ENABLE, "on")
        cfg, params = _model("gemma3-4b")
        lay = kvc.layout_for(cfg, 2, 32, kv_format="bf16")
        sched = Scheduler(params, cfg, lay, chunk_budget=6)
        assert not sched.spec.enabled
        out = _drive(sched, _requests(cfg, n=1, seed=3, max_new=(2, 4)))
        assert out and "spec" not in sched.stats()

    def test_kwarg_on_ring_stack_raises(self):
        cfg, params = _model("gemma3-4b")
        lay = kvc.layout_for(cfg, 2, 32, kv_format="bf16")
        with pytest.raises(ValueError, match="rollback-safe"):
            Scheduler(params, cfg, lay, chunk_budget=6, spec_decode=True)
