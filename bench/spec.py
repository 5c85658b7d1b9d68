"""Find a cell's files by name: ``BENCHMARK.json`` binds a workload to a
model configuration and a traffic mix; each of those, and the cell's
serving parameters, is a data file of its own under ``bench/``.

    BENCHMARK.json                     workloads[name] -> config, traffic, chips
    bench/configs/<config>.json        sizes as run, source, reference module
    bench/configs/<reference>.py       plain float32 reference beside them
    bench/traffic/<traffic>.json       parameters of the one load generator
    bench/cells/<workload>.json        slots, max_seq, KV format, chunk budget,
                                       the numbers compared and their limits

A later cell is added by adding files and entries, never by editing code.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict

ROOT = Path(__file__).resolve().parents[1]
CODE = Path(__file__).resolve().parent  # modules found by name live here

# BENCHMARK.json config keys (Hugging Face names) -> the program's ModelConfig
# fields: the run refuses a program whose model differs from the file
CONFIG_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


@dataclasses.dataclass(frozen=True)
class Cell:
    """Everything one workload needs, read from files alone."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    serving: Dict[str, Any]
    benchmark: Dict[str, Any]
    root: Path

    def end_to_end(self):
        """The end-to-end metrics this cell reports (``--trace 0``)."""
        return [m for m in self.benchmark["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """The per-layer metrics this cell reports (``--trace 1``): those
        listing it, and those without a list whose metric it reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.benchmark["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reference(self):
        """The configuration's plain reference module."""
        return load_module(CODE / "configs" / f"{self.config['reference']}.py")


def _read(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a module of the benchmark by its file path (names of metrics
    and references need not be Python identifiers)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark module {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload_names(root: Path = ROOT):
    return [w["name"] for w in _read(root / "BENCHMARK.json")["workloads"]]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``."""
    bench = _read(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in {root / 'BENCHMARK.json'}"
                       f"; have {sorted(entries)}")
    w = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(root / configs[w["config"]]["file"])
    traffic = _read(root / "bench" / "traffic" / f"{w['traffic']}.json")
    serving = _read(root / "bench" / "cells" / f"{workload}.json")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, serving=serving, benchmark=bench, root=root)


def check_model(cfg, config: Dict[str, Any]) -> None:
    """Raise unless the program's model config matches the file's sizes."""
    bad = [f"{k}: file {config[k]!r}, program {getattr(cfg, f)!r}"
           for k, f in CONFIG_FIELDS.items()
           if k in config and config[k] != getattr(cfg, f)]
    if bad:
        raise ValueError(f"{cfg.name} is not the configuration "
                         f"{config['name']} states: " + "; ".join(bad))
