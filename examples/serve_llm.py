"""End-to-end serving demo: batched requests through the continuous-batching
scheduler (chunked prefill admission + per-slot decode with int8 or
bit-planar BGPP KV cache).

    PYTHONPATH=src python examples/serve_llm.py [--arch phi4-mini-3.8b]
        [--kv-format int8|bf16|bgpp] [--admission chunked|eager]
        [--kv-layout slot|paged] [--page-size 8] [--shared-prefix 16]
        [--weight-format bf16|int8|bstc] [--server]
        [--spec-decode] [--draft-gamma 4] [--draft-planes 4]
        [--chunk-budget 8] [--steps 24] [--batch 4] [--mesh 2,4]

``--server`` swaps the offline replay for the asyncio front door
(``repro.serving.server``) and showcases its three signature moves: a
two-turn chat session whose second turn adopts the first turn's pinned
KV pages through the sha1 prefix index (``--kv-layout paged``), an
interactive arrival preempting a batch prompt's chunked prefill, and a
client that disconnects mid-stream (slot evicted, pages freed, nobody
else perturbed).

Each request is admitted into its own slot of ONE live cache — by default
through fixed-shape prefill chunks (``engine.ChunkedPrefill``, jitted once
per bucket width with the cache donated) interleaved with decode, so slots
already decoding never stall behind a long prompt — and all live slots
decode together in a single batched serve_step per token, the identical
engine code path the decode_32k / long_500k dry-run cells lower for the
production meshes.  Uses the smoke-sized config of the chosen architecture
(CPU container).
"""

import argparse
import time

import numpy as np

import jax

from repro.configs import (ARCH_REGISTRY, WEIGHT_FORMATS,
                           apply_bgpp_overrides,
                           apply_decode_kernel_override,
                           apply_spec_decode_overrides,
                           apply_weight_format_override, get_config)
from repro.launch.serve import device_label
from repro.models import model_zoo
from repro.serving import kv_cache as kvc
from repro.serving import sharded as shd
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler

jax.config.update("jax_platform_name", "cpu")


def run_server_demo(sched, cfg, rng):
    """Drive the asyncio front door end to end: a two-turn chat session
    (turn 2 adopts turn 1's pinned pages on paged layouts), an interactive
    turn preempting a batch prompt's chunked prefill, and a mid-stream
    client disconnect — with the per-step page-leak gate armed."""
    import asyncio

    from repro.serving.server import AsyncServer

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)

    async def collect(stream):
        return [t async for t in stream]

    async def demo():
        server = AsyncServer(sched, check_invariants=True)
        pump = asyncio.ensure_future(server.run())
        t1 = server.chat("demo", prompt(24), 6)
        print(f"[server] chat turn 1 -> {await collect(t1)}")
        # turn 2 races a batch client; the interactive tier preempts its
        # chunked prefill, then turn 2's prompt head comes from the index
        batch = server.submit(prompt(20), 6, priority="batch")
        t2 = server.chat("demo", prompt(8), 6,
                         arrival_step=sched.step_count + 1)
        got2, gotb = await asyncio.gather(collect(t2), collect(batch))
        print(f"[server] chat turn 2 -> {got2} (adopted "
              f"{t2.request.prefix_reused_tokens} history tokens from the "
              f"prefix index)")
        print(f"[server] batch client -> {gotb} "
              f"(prefill preempted {batch.request.preemptions}x)")
        gone = server.submit(prompt(12), 32)
        seen = []
        async for tok in gone:
            seen.append(tok)
            if len(seen) == 2:
                await gone.cancel()
                break
        print(f"[server] disconnecting client got {seen}, then hung up "
              f"(cancelled while {gone.request.cancel_state})")
        server.close_session("demo")
        await server.drain()
        server.close()
        await pump
        return server.stats()

    stats = asyncio.run(demo())
    print(f"[server] totals: finished={stats['finished_requests']} "
          f"cancelled={stats['cancelled_requests']} "
          f"preemptions={stats['preemptions']}")
    for tier, t in stats["tiers"].items():
        print(f"[server] tier {tier}: finished={t['finished']} "
              f"cancelled={t['cancelled']} ttft_s p50={t['ttft_s']['p50']} "
              f"itl_s p50={t['itl_s']['p50']}")
    if "paged" in stats:
        print(f"[server] paged: prefix hit rate "
              f"{stats['paged']['prefix_hit_rate']:.3f}, pages in use "
              f"{stats['paged']['pages_in_use']} (pool drained)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b", choices=sorted(ARCH_REGISTRY))
    ap.add_argument("--kv-format", default="int8", choices=["bf16", "int8", "bgpp"])
    ap.add_argument("--kv-layout", default="slot", choices=["slot", "paged"])
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="shared system-prompt tokens prepended to every "
                         "request (paged layouts reuse their pages)")
    ap.add_argument("--admission", default="chunked", choices=["chunked", "eager"])
    ap.add_argument("--bgpp-rounds", type=int, default=None,
                    help="bgpp progressive rounds (default: config's)")
    ap.add_argument("--bgpp-keep-ratio", type=float, default=None,
                    help="fraction of keys the bgpp decode keeps at full "
                         "precision (default: config's)")
    ap.add_argument("--decode-kernel", default=None,
                    choices=["auto", "jnp", "interpret", "kernel"],
                    help="global-layer decode attend: jnp (legacy) or the "
                         "Pallas paged-attention kernels (default: config's)")
    ap.add_argument("--weight-format", default=None,
                    choices=sorted(WEIGHT_FORMATS),
                    help="serve-time weight numerics for decode projections "
                         "(bf16 raw default; int8/bstc quantized records "
                         "with weight_read pricing) (default: config's)")
    ap.add_argument("--server", action="store_true",
                    help="demo the asyncio front door instead: two-turn "
                         "chat session (prefix-index reuse across turns), "
                         "priority preemption, and a mid-stream disconnect")
    ap.add_argument("--spec-decode", action="store_true",
                    help="bit-plane speculative decoding: truncated-plane "
                         "drafts + batched verify/rollback, bit-identical "
                         "output with an accepted-tokens/step report")
    ap.add_argument("--draft-gamma", type=int, default=None,
                    help="draft tokens per slot per speculative round "
                         "(default: config's)")
    ap.add_argument("--draft-planes", type=int, default=None,
                    help="MSB magnitude bit-planes kept in the draft "
                         "weights, 1-8 (default: config's)")
    ap.add_argument("--chunk-budget", type=int, default=8)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--mesh", default=None,
                    help="DATA,MODEL mesh shape (e.g. 2,4) to shard the "
                         "serve_step: KV pools heads-parallel on model, "
                         "slots on data.  Needs data*model devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 on CPU); default single-device")
    args = ap.parse_args()

    cfg = apply_bgpp_overrides(
        get_config(args.arch, smoke=True),
        rounds=args.bgpp_rounds, keep_ratio=args.bgpp_keep_ratio,
    )
    cfg = apply_decode_kernel_override(cfg, args.decode_kernel)
    cfg = apply_weight_format_override(cfg, args.weight_format)
    cfg = apply_spec_decode_overrides(cfg, enabled=args.spec_decode or None,
                                      gamma=args.draft_gamma,
                                      planes=args.draft_planes)
    if cfg.family not in ("dense", "moe", "vlm"):
        raise SystemExit("this driver serves transformer families; "
                         "see tests/test_serving.py for ssm/hybrid/enc-dec")
    rng = np.random.default_rng(0)
    params, _ = model_zoo.init(jax.random.key(0), cfg)
    max_seq = args.prompt_len + args.steps + 8

    layout = kvc.layout_for(cfg, args.batch, max_seq + args.shared_prefix,
                            kv_format=args.kv_format,
                            layout=args.kv_layout, page_size=args.page_size)
    kw = {}
    if args.mesh:
        d, m = shd.parse_mesh_arg(args.mesh)
        kw["rules"] = shd.rules_for(d, m)
    sched = Scheduler(params, cfg, layout, admission=args.admission,
                      chunk_budget=args.chunk_budget,
                      prefill_kw=dict(block_q=16, block_k=32), **kw)
    print(f"[serve] cache: {kvc.cache_bytes(sched.cache)/1e6:.2f} MB "
          f"({len(layout.global_layers)} global / "
          f"{len(layout.local_layers)} local layers)")

    if args.server:
        run_server_demo(sched, cfg, rng)
        return

    # batched "requests": random prompts of varying length (no tokenizer in
    # the container); +1 because admission itself samples the first token.
    # --shared-prefix prepends one common "system prompt" to all of them and
    # staggers arrivals — prefix reuse needs a resident donor, so a request
    # must arrive after another has prefilled the shared pages.
    prefix = rng.integers(0, cfg.vocab_size, (args.shared_prefix,)).astype(np.int32)
    for rid in range(args.batch):
        plen = max(4, args.prompt_len - 3 * rid)
        sched.submit(Request(
            rid=rid,
            prompt=np.concatenate([
                prefix,
                rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
            ]),
            max_new_tokens=args.steps + 1,
            arrival_step=(args.shared_prefix // 2) * rid,
        ))

    t0 = time.perf_counter()
    sched.run(max_steps=10_000)
    dt = time.perf_counter() - t0
    stats = sched.stats(dt)
    print(f"[serve] arch={cfg.name} kv={args.kv_format} "
          f"admission={args.admission}: decoded {stats['decoded_tokens']} "
          f"tokens across {args.batch} seqs in {dt*1e3:.1f} ms "
          f"({stats['tokens_per_s']:.1f} tok/s on {device_label()}, "
          f"occupancy {stats['mean_occupancy']:.2f})")
    print(f"[serve] ttft_s p50={stats['ttft_s']['p50']} "
          f"p95={stats['ttft_s']['p95']}  itl_s p50={stats['itl_s']['p50']} "
          f"p95={stats['itl_s']['p95']}  "
          f"max prefill tokens/step={stats['max_prefill_tokens_per_step']}")
    kv = stats["kv_read"]
    print(f"[serve] kv read/decode-step: {kv['decode_bytes_per_step']/1e3:.1f}"
          f" kB vs {kv['decode_bf16_equiv_bytes_per_step']/1e3:.1f} kB "
          f"bf16-equivalent ({kv['decode_bytes_reduction_vs_bf16']}x); "
          f"bgpp full rows/slot/layer: "
          f"{kv.get('bgpp', {}).get('full_rows_per_slot', '-')}")
    wr = stats["weight_read"]
    print(f"[serve] weight read/decode-step ({wr['weight_format']}): "
          f"{wr['decode_bytes_per_step']/1e3:.1f} kB vs "
          f"{wr['decode_bf16_equiv_bytes_per_step']/1e3:.1f} kB "
          f"bf16-equivalent ({wr['decode_bytes_reduction_vs_bf16']}x, "
          f"measured/modeled {wr['measured_over_modeled']})")
    if args.mesh:
        print(f"[serve] mesh {kv['mesh']['data']}x{kv['mesh']['model']}: "
              f"{kv['decode_bytes_per_device_per_step']/1e3:.1f} kB/device/"
              f"step over {kv['kv_shards']} kv shards, interconnect "
              f"{kv['interconnect_bytes_per_step']/1e3:.2f} kB/step")
    if "spec" in stats:
        sp = stats["spec"]
        print(f"[serve] spec decode (gamma={sp['gamma']}, "
              f"planes={sp['draft_planes']}): "
              f"accepted/step={sp['accepted_tokens_per_step']:.3f}, "
              f"{sp['accepted_tokens_per_round']:.2f} accepted/round, "
              f"kv {sp['kv_bytes_per_accepted_token']/1e3:.1f} kB and weight "
              f"{sp['weight_bytes_per_accepted_token']/1e3:.1f} kB per "
              f"accepted token")
    if "paged" in stats:
        pg = stats["paged"]
        print(f"[serve] paged: prefix hit rate {pg['prefix_hit_rate']:.3f}, "
              f"resident KV peak {pg['resident_kv_bytes_peak']/1e3:.1f} kB "
              f"vs {pg['slot_resident_kv_bytes']/1e3:.1f} kB slot-dense, "
              f"pages_in_use={pg['pages_in_use']}")
    for req in sorted(sched.finished, key=lambda r: r.rid)[:2]:
        print(f"[serve] seq{req.rid}: {req.generated[:16]}...")


if __name__ == "__main__":
    main()
