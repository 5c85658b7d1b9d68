"""Throwaway benchmark roots made of data files alone, at smoke size: the
registry's smoke variants as configurations, tiny traffic mixes, and cells
with a small cache."""

from __future__ import annotations

import json
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

SMOKE_CONFIGS = {
    "phi4-smoke": dict(registry="phi4-mini-3.8b", hidden_size=96,
                       intermediate_size=192, num_hidden_layers=2,
                       num_attention_heads=6, num_key_value_heads=2,
                       tie_word_embeddings=True),
    "deepseek-smoke": dict(registry="deepseek-7b", hidden_size=64,
                           intermediate_size=160, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=4,
                           tie_word_embeddings=False),
}


def write_root(root: Path, cells) -> Path:
    """A benchmark root holding ``BENCHMARK.json`` and the data files of
    ``cells``: ``[(workload, config, traffic, cell overrides)]``, every
    config a smoke variant of a registry model, every traffic mix one of
    ``tiny-open`` (short requests) and ``tiny-long`` (long outputs)."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for d in ("configs", "traffic", "cells"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    bench["configs"], bench["workloads"] = [], []
    for name, conf in SMOKE_CONFIGS.items():
        D = conf["hidden_size"] // conf["num_attention_heads"]
        (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(
            dict(conf, name=name, variant="smoke", reference="dense_decoder",
                 head_dim=D, vocab_size=512, rope_theta=10000.0,
                 rms_norm_eps=1e-6, mesh=[1, 1])))
        bench["configs"].append({"name": name, "source": "smoke",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "smoke"})
    (root / "bench" / "traffic" / "tiny-open.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 6.0, "strata": 4, "order_seed": 1,
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                   "min": 4, "max": 60},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 3, "max": 16}}))
    (root / "bench" / "traffic" / "tiny-long.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 2.0, "strata": 4, "order_seed": 1,
        "prompt": {"dist": "uniform", "min": 20, "max": 50},
        "output": {"dist": "uniform", "min": 40, "max": 60}}))
    names = []
    for workload, config, traffic, over in cells:
        bench["workloads"].append({"name": workload, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "smoke"})
        serving = dict({"kv_format": "int8", "kv_layout": "slot", "slots": 4,
                        "max_seq": 128, "chunk_budget": 16, "warm_s": 0.5,
                        "trace_s": 0.5,
                        "check": {"tokens": 24, "attention": "int8",
                                  "max_logit_gap": 0.05}},
                       **over)
        (root / "bench" / "cells" / f"{workload}.json").write_text(
            json.dumps(serving))
        names.append(workload)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = names
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
