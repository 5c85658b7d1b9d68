"""Property-based tests of system invariants (hypothesis)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing.hypcompat import given, settings, st

from repro.analysis.roofline import bgpp_kernel_traffic, bstc_weight_traffic
from repro.configs import apply_weight_format_override, get_config
from repro.configs.base import ModelConfig
from repro.core import attention, bstc
from repro.models import model_zoo, moe
from repro.serving import kv_cache as kvc
from repro.serving import sharded as shd
from repro.serving import weights as swt
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler

jax.config.update("jax_platform_name", "cpu")


def moe_cfg(E, k, cf=8.0):
    return ModelConfig(
        name="t", family="moe", num_layers=1, d_model=16, num_heads=2,
        num_kv_heads=2, head_dim=8, d_ff=32, vocab_size=64,
        num_experts=E, experts_per_token=k, moe_capacity_factor=cf,
        dtype="float32",
    )


class TestMoEDispatchInvariants:
    @given(
        st.integers(0, 2**31 - 1),
        st.sampled_from([(4, 1), (4, 2), (8, 2)]),
    )
    @settings(max_examples=12, deadline=None)
    def test_dropless_capacity_is_exact_weighted_sum(self, seed, ek):
        """With dropless capacity, the MoE output equals the explicit
        dense-expert weighted sum — no token lost, duplicated or misrouted."""
        E, k = ek
        cfg = moe_cfg(E, k, cf=float(E))  # capacity >= all tokens
        rng = np.random.default_rng(seed)
        params, _ = moe.moe_init(jax.random.key(seed % 1000), cfg, jnp.float32)
        x = jnp.asarray(rng.normal(size=(2, 4, cfg.d_model)), jnp.float32)

        y, _ = moe.moe_apply(params, x, cfg)

        # dense reference: run every expert on every token, combine by gate
        xt = x.reshape(-1, cfg.d_model)
        logits = xt @ params["router"]
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        gv, ei = jax.lax.top_k(probs, k)
        if k > 1:
            gv = gv / jnp.sum(gv, -1, keepdims=True)
        outs = []
        for e in range(E):
            g = xt @ params["gate"][e]
            u = xt @ params["up"][e]
            outs.append((jax.nn.silu(g) * u) @ params["down"][e])
        outs = jnp.stack(outs, 1)  # (T, E, D)
        ref = jnp.zeros_like(xt)
        for j in range(k):
            ref = ref + gv[:, j : j + 1] * jnp.take_along_axis(
                outs, ei[:, j : j + 1, None], axis=1
            )[:, 0]
        np.testing.assert_allclose(
            np.asarray(y).reshape(-1, cfg.d_model), np.asarray(ref),
            rtol=2e-4, atol=2e-4,
        )

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=8, deadline=None)
    def test_capacity_drop_only_shrinks(self, seed):
        """Dropping tokens (small capacity) must never create output where
        the dropless version had none, and dropped tokens output ~0 from
        the routed component."""
        cfg_full = moe_cfg(4, 1, cf=4.0)
        cfg_tight = moe_cfg(4, 1, cf=0.25)
        rng = np.random.default_rng(seed)
        params, _ = moe.moe_init(jax.random.key(1), cfg_full, jnp.float32)
        x = jnp.asarray(rng.normal(size=(1, 8, cfg_full.d_model)), jnp.float32)
        y_full, _ = moe.moe_apply(params, x, cfg_full)
        y_tight, _ = moe.moe_apply(params, x, cfg_tight)
        nf = np.linalg.norm(np.asarray(y_full).reshape(8, -1), axis=1)
        nt = np.linalg.norm(np.asarray(y_tight).reshape(8, -1), axis=1)
        assert (nt <= nf + 1e-4).all()


class TestBlockedAttendEquivalence:
    @given(
        st.integers(0, 2**31 - 1),
        st.sampled_from(["causal", "sliding", "chunked", "full"]),
        st.sampled_from([(8, 8), (16, 4), (4, 16)]),
    )
    @settings(max_examples=12, deadline=None)
    def test_matches_dense_attend(self, seed, kind, blocks):
        rng = np.random.default_rng(seed)
        B, S, Hq, Hk, D = 1, 32, 4, 2, 8
        q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, Hk, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, Hk, D)), jnp.float32)
        w = 8 if kind in ("sliding", "chunked") else 0
        bq, bk = blocks
        got = attention.blocked_attend(
            q, k, v, mask_kind=kind, window=w, block_q=bq, block_k=bk
        )
        mask = attention.make_mask(kind, S, S, w)
        want = attention.attend(q, k, v, mask=mask)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_nondivisible_lengths(self):
        rng = np.random.default_rng(0)
        B, Sq, Sk, H, D = 1, 21, 37, 2, 8
        q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, Sk, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, Sk, H, D)), jnp.float32)
        got = attention.blocked_attend(
            q, k, v, mask_kind="full", block_q=8, block_k=16
        )
        want = attention.attend(q, k, v, mask=jnp.ones((Sq, Sk), bool))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )


class TestBGPPKernelTrafficModel:
    @given(st.sampled_from([1024, 32768]), st.floats(0.05, 0.9))
    @settings(max_examples=20, deadline=None)
    def test_reduction_bounds(self, S, keep):
        out = bgpp_kernel_traffic(S, 128, rounds=4, keep_ratio=keep)
        assert out["bgpp_kernel_bytes"] > 0
        # bounded above by prediction + full-precision refetch of the keeps
        # (at keep→1 BGPP costs MORE than dense — the paper's sparsity
        # premise is what makes it pay), and saves >=1.5x at paper settings
        assert out["bgpp_kernel_bytes"] < 3.6 * S * 128
        if keep <= 0.25:
            assert out["reduction"] > 1.5

    def test_monotone_in_keep_ratio(self):
        r = [
            bgpp_kernel_traffic(32768, 128, keep_ratio=k)["reduction"]
            for k in (0.125, 0.25, 0.5, 0.9)
        ]
        assert r[0] > r[1] > r[2] > r[3]


def _projection_shards(cfg, mesh):
    """What the serving placement makes of ``cfg``'s projections on a
    ``(data, model)`` mesh (shapes only: no devices needed)."""
    shapes = jax.eval_shape(lambda k: model_zoo.init(k, cfg)[0],
                            jax.random.key(0))
    return shd.projection_shards(shapes, model_zoo.param_specs(cfg), mesh)


class TestKVReadAccountingLaws:
    """Laws of the mesh columns in the kv-read accounting
    (kv_cache.decode_read_bytes / chunk_read_bytes): per-device shares
    recombine to the single-device totals, interconnect bytes vanish
    exactly at mesh 1x1, and the two tensor-parallel all-reduces of each
    layer, priced from the placement's shard counts, grow monotonically
    with the "model" size and match a hand count."""

    # deepseek smoke: 4 q / 4 kv heads — divisible by every model size here
    CFG = get_config("deepseek-7b", smoke=True)

    def _shards(self, mesh):
        return _projection_shards(self.CFG, mesh)

    def _layout(self, fmt, layout, slots=4):
        return kvc.layout_for(self.CFG, slots, 48, kv_format=fmt,
                              layout=layout, page_size=8)

    @given(
        st.sampled_from(["bf16", "int8", "bgpp"]),
        st.sampled_from(["slot", "paged"]),
        st.sampled_from([(1, 1), (2, 1), (1, 2), (1, 4), (2, 4), (4, 2)]),
    )
    @settings(max_examples=24, deadline=None)
    def test_per_device_times_shards_is_total(self, fmt, layout, mesh):
        lay = self._layout(fmt, layout)
        out = kvc.decode_read_bytes(lay, self.CFG, mesh)
        pd = out["per_device"]
        assert pd["shards"] == mesh[0] * mesh[1]  # all dims divide here
        np.testing.assert_allclose(pd["total"] * pd["shards"], out["total"])
        np.testing.assert_allclose(
            pd["global"] + pd["local"], pd["total"])
        ck = kvc.chunk_read_bytes(lay, self.CFG, mesh)
        np.testing.assert_allclose(
            ck["per_device"]["total"] * ck["per_device"]["shards"],
            ck["total"])

    @given(st.sampled_from(["bf16", "int8", "bgpp"]),
           st.sampled_from(["slot", "paged"]))
    @settings(max_examples=6, deadline=None)
    def test_interconnect_zero_at_1x1(self, fmt, layout):
        lay = self._layout(fmt, layout)
        for reader in (kvc.decode_read_bytes, kvc.chunk_read_bytes):
            ic = reader(lay, self.CFG, (1, 1),
                        shards=self._shards((1, 1)))["interconnect"]
            assert ic["total"] == 0.0, (reader.__name__, ic)

    @given(st.sampled_from(["bf16", "int8", "bgpp"]),
           st.sampled_from(["slot", "paged"]))
    @settings(max_examples=6, deadline=None)
    def test_attend_allgather_monotone_in_model(self, fmt, layout):
        """Each ring all-reduce moves 2 (m - 1) / m of its rows from each
        of m devices — strictly more bytes at every larger dividing model
        size, after wo and after the MLP alike, in decode and in a
        chunk."""
        lay = self._layout(fmt, layout)
        for key in ("attn_out_allreduce", "mlp_out_allreduce"):
            dec = [kvc.decode_read_bytes(lay, self.CFG, (1, m),
                                         self._shards((1, m)))
                   ["interconnect"][key] for m in (1, 2, 4)]
            ck = [kvc.chunk_read_bytes(lay, self.CFG, (1, m), chunk_width=8,
                                       shards=self._shards((1, m)))
                  ["interconnect"][key] for m in (1, 2, 4)]
            assert dec[0] == ck[0] == 0.0
            assert dec[0] < dec[1] < dec[2]
            assert ck[0] < ck[1] < ck[2]

    @pytest.mark.parametrize("mesh", [(1, 2), (1, 4), (2, 4), (4, 2)])
    def test_allreduce_bytes_by_hand(self, mesh):
        """Two float32 all-reduces of (rows, d_model) per layer, each moving
        2 (m - 1) rows x d_model x 4 bytes over a "model" group: the
        decode step's B rows split over "data"; a B=1 chunk's C rows
        reduced again by every data replica."""
        d, m = mesh
        lay = self._layout("int8", "slot")
        L, D = self.CFG.num_layers, self.CFG.d_model
        shards = self._shards(mesh)
        ic = kvc.decode_read_bytes(lay, self.CFG, mesh,
                                   shards)["interconnect"]
        want = 2 * (m - 1) * lay.batch * D * 4 * L
        assert ic["attn_out_allreduce"] == ic["mlp_out_allreduce"] == want
        assert ic["total"] == 2 * want
        ck = kvc.chunk_read_bytes(lay, self.CFG, mesh, chunk_width=16,
                                  shards=shards)["interconnect"]
        assert ck["attn_out_allreduce"] == 2 * (m - 1) * 16 * D * 4 * L * d
        assert ck["total"] == 2 * ck["attn_out_allreduce"]

    def test_indivisible_shapes_fall_back_to_replication(self):
        # phi4 smoke: 6 q / 2 kv heads — neither divides model=4, so the
        # KV model factor must collapse to 1 (pool replicated); wo's 96
        # input rows and the MLP's 192 FF still divide 4, so both
        # all-reduces remain, and the unsplit batch of 3 is reduced by
        # both data replicas
        cfg = get_config("phi4-mini-3.8b", smoke=True)
        lay = kvc.layout_for(cfg, 3, 48, kv_format="bf16", layout="slot")
        d_eff, m_eff = kvc.mesh_shard_factors(lay, cfg, (2, 4))
        assert m_eff == 1
        assert d_eff == 1  # batch 3 does not divide data=2 either
        out = kvc.decode_read_bytes(lay, cfg, (2, 4),
                                    _projection_shards(cfg, (2, 4)))
        assert out["per_device"]["shards"] == 1
        ring = 2 * 3 * 3 * cfg.d_model * 4 * cfg.num_layers * 2
        assert out["interconnect"]["attn_out_allreduce"] == ring
        assert out["interconnect"]["total"] == 2 * ring
        # a d_ff that 4 does not divide keeps down replicated: nothing to sum
        odd = dataclasses.replace(cfg, d_ff=cfg.d_ff + 2)
        shards = _projection_shards(odd, (2, 4))
        assert shards["down"] == shards["up"] == (1, 1)
        ic = kvc.decode_read_bytes(lay, odd, (2, 4), shards)["interconnect"]
        assert ic["mlp_out_allreduce"] == 0.0 and ic["attn_out_allreduce"]

    def test_chunk_paged_write_broadcast_is_zero(self):
        """B=1 prefill chunks are replicated over "data": every replica
        computes the chunk and writes its own pool copy, so the paged
        write broadcast term prices nothing (unlike decode, whose batch
        rows live on distinct data shards)."""
        lay = self._layout("int8", "paged")
        for mesh in ((2, 1), (2, 4), (4, 2)):
            shards = self._shards(mesh)
            ck = kvc.chunk_read_bytes(lay, self.CFG, mesh, shards=shards)
            assert ck["interconnect"]["paged_write_bcast"] == 0.0
            dk = kvc.decode_read_bytes(lay, self.CFG, mesh, shards)
            assert dk["interconnect"]["paged_write_bcast"] > 0.0


class TestWeightReadAccountingLaws:
    """Laws of the serve-time weight-read plan (repro.serving.weights):
    per-projection device shares, priced from the placement's shard
    counts, recombine to the single-device total on every mesh, the bf16 plan prices the raw dense bytes exactly, bstc
    coding halves (better) the bf16 traffic at the paper's bit-level
    sparsity, and the closed-form traffic model is the measured stream's
    formula (the bench's ±10% reconciliation gate rides on that)."""

    _CACHE = {}

    @classmethod
    def _plan(cls, fmt):
        if fmt not in cls._CACHE:
            # deepseek smoke: 4 q / 4 kv heads — divisible by every model
            # size below (same geometry the kv-read laws lean on)
            cfg = apply_weight_format_override(
                get_config("deepseek-7b", smoke=True), fmt)
            params, _ = model_zoo.init(jax.random.key(0), cfg)
            lay = kvc.layout_for(cfg, 4, 48, kv_format="bf16")
            _, plan = swt.prepare_serve_params(params, cfg, lay, fmt)
            cls._CACHE[fmt] = (cfg, params, plan)
        return cls._CACHE[fmt]

    @given(
        st.sampled_from(["bf16", "int8", "bstc"]),
        st.sampled_from([(1, 1), (2, 1), (1, 2), (1, 4), (2, 4), (4, 2)]),
    )
    @settings(max_examples=18, deadline=None)
    def test_per_device_times_shards_is_total(self, fmt, mesh):
        cfg, params, plan = self._plan(fmt)
        out = plan.decode_read_bytes(
            shd.projection_shards(params, model_zoo.param_specs(cfg), mesh))
        recomposed = sum(
            out["per_device_by_projection"][p]
            * out["shards_by_projection"][p]
            for p in out["per_device_by_projection"]
        )
        np.testing.assert_allclose(recomposed, out["total"])
        np.testing.assert_allclose(out["total"], plan.total_bytes)
        np.testing.assert_allclose(
            sum(out["per_projection"].values()), out["total"])
        # tensor parallel: every projection divides the model size here,
        # so each device reads 1/model of the whole plan
        np.testing.assert_allclose(out["per_device"]["total"],
                                   out["total"] / mesh[1])
        assert out["per_device"]["replicated"] == (
            0.0 if mesh[1] > 1 else out["total"])

    @pytest.mark.parametrize("proj,placement", [
        ("wq", "column"), ("wk", "column"), ("wv", "column"), ("wo", "row"),
        ("gate", "column"), ("up", "column"), ("down", "row"),
        ("lm_head", "vocab")])
    def test_placement_of_each_projection(self, proj, placement):
        """On [1, 4] each projection splits 4 ways along the axis its
        placement names: (input-row, output-column) shards."""
        cfg, params, _ = self._plan("bf16")
        want = {"column": (1, 4), "row": (4, 1), "vocab": (1, 4)}[placement]
        assert shd.projection_shards(
            params, model_zoo.param_specs(cfg), (1, 4))[proj] == want

    def test_bf16_plan_prices_dense_bytes_exactly(self):
        cfg, _, plan = self._plan("bf16")
        db = 2 if cfg.dtype == "bfloat16" else 4
        for e in plan.entries:
            want = db * e.copies * e.in_dim * e.out_dim
            assert e.coded_bytes == e.bf16_bytes == want, e.path

    def test_bstc_coded_at_most_half_of_bf16(self):
        _, _, plan = self._plan("bstc")
        assert plan.total_bytes <= plan.bf16_bytes / 2, (
            "BSTC coding must at least halve bf16 weight traffic at the "
            "paper's bit-level sparsity")
        out = plan.decode_read_bytes()
        assert 0.9 <= out["total"] / out["modeled"] <= 1.1, (
            "measured coded stream must reconcile with the closed form")

    @given(st.sampled_from([0.7, 0.8, 0.95]), st.sampled_from([2, 4, 8]))
    @settings(max_examples=9, deadline=None)
    def test_traffic_model_matches_closed_form(self, sc, m):
        nbits, in_dim, out_dim = 7, 128, 64
        out = bstc_weight_traffic(
            in_dim, out_dim, m=m, nbits=nbits, col_sparsity=[sc] * nbits)
        n = in_dim * out_dim
        bits = n + nbits * n / bstc.compression_ratio_closed_form(m, sc)
        np.testing.assert_allclose(out["bstc_bytes"], bits / 8 + 4 * out_dim)

    def test_traffic_model_monotone_in_sparsity(self):
        vals = [
            bstc_weight_traffic(128, 64, col_sparsity=[s] * 7)["bstc_bytes"]
            for s in (0.65, 0.8, 0.95)
        ]
        assert vals[0] > vals[1] > vals[2]
        # raw pricing (no sparsity) is plain int8 + scales
        raw = bstc_weight_traffic(128, 64)
        np.testing.assert_allclose(raw["bstc_bytes"], raw["int8_bytes"])


class TestSpecDecodeAccountingLaws:
    """Laws of the speculative-decoding counters (Scheduler._spec_round):
    per-slot-round acceptance is bounded by gamma + 1, per-request rows
    reconcile with the global counters and with the kv/weight byte
    totals, bytes-per-accepted-token is exactly bytes-per-step divided by
    the acceptance rate, perfect drafts bank gamma + 1 tokens every full
    round, and adversarially-wrong drafts degrade to exactly the one
    corrected token per round — never worse, never silently better."""

    GAMMA = 3
    _CACHE = {}

    @classmethod
    def _runs(cls):
        """One reference (non-spec) run plus three speculative runs over
        the SAME deterministic trace: perfect drafts (callback feeding the
        reference's own tokens), adversarial drafts (always wrong), and
        truncated-plane drafts — cached; every law reads these."""
        if not cls._CACHE:
            cfg = get_config("phi4-mini-3.8b", smoke=True)
            params, _ = model_zoo.init(jax.random.key(0), cfg)
            lay = kvc.layout_for(cfg, 2, 48, kv_format="bf16")
            rng = np.random.default_rng(0)
            protos = [Request(
                rid=i,
                prompt=rng.integers(
                    0, cfg.vocab_size, (int(rng.integers(4, 14)),)
                ).astype(np.int32),
                # budgets straddle multiples of gamma + 1 so perfect
                # drafts produce both full and truncated final rounds
                max_new_tokens=[8, 9, 5][i],
                arrival_step=3 * i,
            ) for i in range(3)]

            def clones():
                return [Request(rid=r.rid, prompt=r.prompt,
                                max_new_tokens=r.max_new_tokens,
                                arrival_step=r.arrival_step)
                        for r in protos]

            def drive(sched, reqs):
                for r in reqs:
                    sched.submit(r)
                sched.run(max_steps=1000)
                assert len(sched.finished) == len(reqs)
                return sched, {r.rid: r for r in sched.finished}

            ref, truth = drive(
                Scheduler(params, cfg, lay, chunk_budget=6,
                          spec_decode=False), clones())
            tokens = {rid: list(r.generated) for rid, r in truth.items()}
            shared = ref.shared_fns()
            g = cls.GAMMA

            def perfect(req, t):
                seq = tokens[req.rid]
                return seq[t] if t < len(seq) else 0

            def adversarial(req, t):
                seq = tokens[req.rid]
                true = seq[t] if t < len(seq) else 0
                return (true + 1) % cfg.vocab_size

            cls._CACHE["truth"] = tokens
            for name, kw in (
                ("perfect", {"draft_fn": perfect}),
                ("adversarial", {"draft_fn": adversarial}),
                ("planes", {"draft_planes": 2}),
            ):
                sched, fin = drive(
                    Scheduler(params, cfg, lay, chunk_budget=6,
                              spec_decode=True, draft_gamma=g,
                              shared_fns=shared, **kw), clones())
                cls._CACHE[name] = (sched, fin)
        return cls._CACHE

    def test_outputs_bit_identical_to_reference(self):
        runs = self._runs()
        for name in ("perfect", "adversarial", "planes"):
            _, fin = runs[name]
            for rid, seq in runs["truth"].items():
                assert fin[rid].generated == seq, (name, rid)

    def test_accepted_bounded_by_gamma_plus_one(self):
        runs = self._runs()
        for name in ("perfect", "adversarial", "planes"):
            _, fin = runs[name]
            for r in fin.values():
                assert r.spec_accepts, (name, r.rid)
                assert all(1 <= a <= self.GAMMA + 1
                           for a in r.spec_accepts), (name, r.rid,
                                                      r.spec_accepts)

    def test_per_request_rows_reconcile_with_globals(self):
        runs = self._runs()
        for name in ("perfect", "adversarial", "planes"):
            sched, fin = runs[name]
            reqs = list(fin.values())
            assert sum(sum(r.spec_accepts) for r in reqs) \
                == sched.spec_accepted, name
            assert sum(len(r.spec_accepts) for r in reqs) \
                == sched.spec_slot_rounds, name
            assert sum(r.spec_drafted for r in reqs) == sched.spec_drafted
            assert sched.spec_drafted \
                == self.GAMMA * sched.spec_slot_rounds, name
            for r in reqs:
                # every decode-path token was accepted in some round (the
                # first token comes from prefill, not from decode)
                assert sum(r.spec_accepts) == len(r.generated) - 1, \
                    (name, r.rid)

    def test_counters_reconcile_with_byte_totals(self):
        runs = self._runs()
        for name in ("perfect", "adversarial", "planes"):
            sched, _ = runs[name]
            stats = sched.stats()
            sp, kv, wr = stats["spec"], stats["kv_read"], \
                stats["weight_read"]
            assert sp["accepted_tokens"] == stats["decoded_tokens"], name
            assert kv["decode_steps"] \
                == sp["draft_steps"] + sp["verify_steps"], name
            assert kv["decode_bytes"] \
                == kv["decode_steps"] * kv["decode_bytes_per_step"], name
            if name != "planes":  # callback drafts run no device steps
                assert sp["draft_steps"] == 0, name
                np.testing.assert_allclose(
                    sp["modeled_weight_bytes_per_accepted_token"],
                    sp["weight_bytes_per_accepted_token"], atol=1)

    def test_bytes_per_accepted_is_per_step_over_acceptance_rate(self):
        runs = self._runs()
        for name in ("perfect", "adversarial", "planes"):
            sched, _ = runs[name]
            stats = sched.stats()
            sp, kv, wr = stats["spec"], stats["kv_read"], \
                stats["weight_read"]
            rate = sp["accepted_tokens"] / kv["decode_steps"]
            np.testing.assert_allclose(
                kv["decode_bytes"] / sp["accepted_tokens"],
                kv["decode_bytes_per_step"] / rate, rtol=1e-12)
            np.testing.assert_allclose(
                wr["decode_bytes"] / sp["accepted_tokens"],
                wr["decode_bytes_per_step"] / rate, rtol=1e-12)

    def test_perfect_drafts_accept_gamma_plus_one_per_full_round(self):
        _, fin = self._runs()["perfect"]
        for r in fin.values():
            # every round except the request's last banks gamma + 1; the
            # final round is truncated only by the decode budget
            assert all(a == self.GAMMA + 1 for a in r.spec_accepts[:-1]), \
                (r.rid, r.spec_accepts)
            assert sum(r.spec_accepts) == len(r.generated) - 1
        sched, _ = self._runs()["perfect"]
        assert sched.spec_max_accept == self.GAMMA + 1

    def test_adversarial_drafts_accept_exactly_one_per_round(self):
        sched, fin = self._runs()["adversarial"]
        for r in fin.values():
            assert all(a == 1 for a in r.spec_accepts), (r.rid,
                                                         r.spec_accepts)
        sp = sched.stats()["spec"]
        assert sp["accepted_tokens_per_round"] == 1.0
        assert sp["draft_hit_rate"] == 0.0


class TestDispatchRoundTripLaws:
    """Round-trip laws for the kernel dispatch paths.

    Small shapes + few examples keep these inside the tier-1 budget; the
    exhaustive tiling sweeps live in tests/test_kernel_*.py and
    tests/test_kernel_dispatch.py.
    """

    @given(st.integers(0, 2**31 - 1), st.sampled_from([0.05, 0.3, 0.8]))
    @settings(max_examples=6, deadline=None)
    def test_bstc_encode_decode_identity(self, seed, density):
        """BSTC encode -> dispatch-routed decode is the identity on group
        patterns, in both interpret and ref modes."""
        from repro.kernels.bstc_decode import (
            bstc_decode_patterns, prepare_encoded_plane,
        )

        rng = np.random.default_rng(seed)
        plane = (rng.random((8, 512)) < density).astype(np.uint8)
        enc = bstc.encode_plane(plane, m=4)
        ops = prepare_encoded_plane(enc)
        want = np.asarray(bstc.decode_plane(enc))
        for mode in ("interpret", "ref"):
            patt = bstc_decode_patterns(ops, tile_g=4, mode=mode)
            rows = np.asarray(bstc.expand_patterns(patt, m=4))
            np.testing.assert_array_equal(rows, want, err_msg=f"mode={mode}")

    @given(st.integers(0, 2**31 - 1), st.sampled_from([1, 2, 4]))
    @settings(max_examples=6, deadline=None)
    def test_brcr_factorization_matches_dense_gemm(self, seed, m):
        """BRCR's enumeration factorization == dense GEMM, exactly, on int
        inputs — through the dispatch layer in interpret and ref modes."""
        from repro.kernels.brcr_gemm import brcr_gemm, prepare_brcr_operands

        rng = np.random.default_rng(seed)
        M, H, N = 16, 128, 8
        w = np.round(np.clip(rng.normal(size=(M, H)) * 40, -127, 127)).astype(
            np.int8
        )
        x = jnp.asarray(rng.integers(-100, 100, size=(H, N)), jnp.float32)
        ops = prepare_brcr_operands(w, m=m)
        ref = np.asarray(w, np.int64) @ np.asarray(x, np.int64)
        for mode in ("interpret", "ref"):
            y = brcr_gemm(
                ops, x, tile_m=M, tile_k=H, tile_n=N, mode=mode
            )
            np.testing.assert_array_equal(
                np.asarray(y, np.int64), ref, err_msg=f"mode={mode}"
            )

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=4, deadline=None)
    def test_bstc_matmul_weight_reconstruction_roundtrip(self, seed):
        """prepare -> reconstruct_dense_weight is the identity on int8
        weights (the ref dispatch path's premise)."""
        from repro.kernels.bstc_matmul import prepare_bstc_matmul_operands
        from repro.kernels.bstc_matmul.ops import reconstruct_dense_weight

        rng = np.random.default_rng(seed)
        w = np.round(
            np.clip(rng.normal(size=(8, 512)) * 30, -127, 127)
        ).astype(np.int8)
        ops = prepare_bstc_matmul_operands(w, m=4)
        got = np.asarray(reconstruct_dense_weight(ops))
        np.testing.assert_array_equal(got, w.astype(np.int32))
