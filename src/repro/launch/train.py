"""Production training launcher: mesh + sharded state + data pipeline +
checkpoint/restore + heartbeat + straggler monitoring + resilient loop.

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --smoke \\
        --steps 100 --batch 8 --seq-len 128 [--data 1 --model 1] \\
        [--ckpt-dir DIR] [--int8-opt] [--fsdp]

``--smoke`` trains the config's reduced variant (the CPU tests); without it
the model has its published widths.  On a real cluster the same entry point
takes the production mesh (launch/mesh.py) — every component (rules,
train_step, checkpointer, pipeline) is identical to what the dry-run lowers
for 16×16 / 2×16×16.
"""

from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import ARCH_REGISTRY, get_config
from repro.data import Prefetcher, SyntheticLMDataset
from repro.distributed import sharding as sh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model_zoo
from repro.optim import AdamWConfig, adamw_init, opt_state_specs
from repro.runtime import Heartbeat, StragglerMonitor, run_resilient
from repro.training import make_train_step


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCH_REGISTRY), default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="train the config's reduced smoke variant (CPU "
                         "tests) instead of the published widths")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--int8-opt", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "mcbp_train"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--heartbeat", default=os.path.join(
        tempfile.gettempdir(), "mcbp_train_heartbeat.json"))
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_mesh(args.data, args.model)
    rules = sh.rules_for_mesh(
        mesh, fsdp_axes=(sh.D_MODEL,) if args.fsdp else (), sp=args.model > 1
    )
    opt_cfg = AdamWConfig(
        peak_lr=3e-4, warmup_steps=min(50, args.steps // 4),
        decay_steps=args.steps,
        state_dtype="int8" if args.int8_opt else "fp32",
    )

    params, p_specs = model_zoo.init(jax.random.key(0), cfg)
    state = {"params": params, "opt": adamw_init(params, opt_cfg)}
    state_specs = {"params": p_specs, "opt": opt_state_specs(p_specs, opt_cfg)}
    state = jax.device_put(state, rules.tree_shardings(mesh, state_specs, state))

    fwd_kw = dict(block_q=64, block_k=128, remat=True)
    if cfg.family == "ssm":
        fwd_kw = dict(chunk=64, remat=True)
    elif cfg.family == "hybrid":
        fwd_kw["ssd_chunk"] = 64
    step_fn = jax.jit(
        make_train_step(cfg, rules, opt_cfg, fwd_kw,
                        grad_accum=args.grad_accum, param_specs=p_specs),
        donate_argnums=(0,),
    )

    modality = {}
    if cfg.family == "vlm":
        modality["vision"] = (cfg.vision_tokens, cfg.d_vision)
    if cfg.family == "enc_dec":
        modality["frames"] = (cfg.encoder_seq, cfg.d_audio)
    ds = SyntheticLMDataset(
        cfg.vocab_size, args.seq_len, args.batch, seed=0, modality=modality
    )
    ckpt = Checkpointer(args.ckpt_dir, keep=3)
    hb = Heartbeat(args.heartbeat, interval_s=10.0,
                   payload={"arch": cfg.name}).start()
    monitor = StragglerMonitor(threshold=8.0)

    start = ckpt.latest_step() or 0
    if start:
        start, state = ckpt.restore(state)
        print(f"[train] restored step {start} from {args.ckpt_dir}")
    holder = {"state": state}
    pf = Prefetcher(ds, depth=2, start_step=start)

    def train_one(step):
        got_step, batch = pf.next()
        assert got_step == step, (got_step, step)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        t0 = time.perf_counter()
        with mesh:
            holder["state"], metrics = step_fn(holder["state"], batch)
        dt = time.perf_counter() - t0
        monitor.record(step, dt)
        if step % 10 == 0:
            print(f"[train] step {step:5d} loss {float(metrics['loss']):8.4f} "
                  f"lr {float(metrics['lr']):.2e} ({dt*1e3:.0f} ms)")
        if step and step % args.ckpt_every == 0:
            ckpt.save(step + 1, holder["state"])
        hb.beat(step=step)

    def restore():
        nonlocal pf
        step, holder["state"] = ckpt.restore(holder["state"])
        pf.close()
        pf = Prefetcher(ds, depth=2, start_step=step)
        return step

    try:
        failures = run_resilient(train_one, start, args.steps - start, restore)
        print(f"[train] done ({failures} failures survived); "
              f"median step {monitor.median*1e3:.0f} ms")
        ckpt.save(args.steps, holder["state"])
        ckpt.wait()
    finally:
        pf.close()
        hb.stop()


if __name__ == "__main__":
    main()
