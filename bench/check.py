"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the program finished, drawn from the seed and always holding
the longest, is run through the configuration's plain float32 reference:
each prompt with the tokens the client received, teacher-forced.  The
reference attends with the arithmetic the cell's file states for its KV
format (``check.attention``: ``"int8"`` stores K and V, and quantizes each
query row and each row of weighted probabilities, in int8 as the paper's
int8 format does), so that what the cell states is not counted as error.
For every served token the reference gives the gap by which that token's
logit lies below its own best; a greedy server that computes what the
model says serves gaps of rounding size, a wrong page, position or head
serves gaps of the logits' own size.  The served rows' logits are kept at
``COLUMNS`` seeded vocabulary columns, and their squared error against the
reference's, over the reference's mean square, is ``logit_mse_rel``: the
number that a lower precision (the control) moves most.  The widest gap,
the mean gap, the share of tokens off the reference's argmax and that
error are compared with the limits the cell's file gives for them
(``bench/cells/<workload>.json``, ``check``), which ``PERF.md`` derives
from the program's readings and its control's.

Besides: every finished request received all the tokens it asked for, the
client's stream holds exactly the scheduler's tokens, and no request due in
the window failed.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, Optional

import numpy as np


NOTHING = 1e9
COLUMNS = 1024  # vocabulary columns of every served row kept for the check


def columns(vocab: int, seed: int) -> np.ndarray:
    """The vocabulary columns whose served logits are kept: drawn from the
    seed, the same for every request of a run."""
    rng = np.random.default_rng(int(seed) + 2)
    return np.sort(rng.choice(vocab, size=min(COLUMNS, vocab), replace=False))


def sample(records, seed: int, tokens: int):
    """Finished requests to compare: the longest, then others in an order
    drawn from the seed, until ``tokens`` served tokens are covered."""
    done = [r for r in records
            if r.request is not None and not r.request.cancelled and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: -len(r.tokens))
    rng = np.random.default_rng(int(seed) + 1)
    picked = [done[0]] + [done[1 + i] for i in rng.permutation(len(done) - 1)]
    out, n = [], 0
    for r in picked:
        out.append(r)
        n += len(r.tokens)
        if n >= tokens:
            break
    return out


def _reference_conf(cell, **kw):
    """The configuration as the reference computes it: with the attention
    arithmetic the cell states for its KV format (``"int8"``: K, V,
    queries and weighted probabilities in int8), else float32."""
    return dict(cell.config,
                kv_format=cell.serving["check"].get("attention", "float32"),
                **kw)


def _numbers(gaps, err: float, ref2: float) -> Dict:
    """The numbers a cell's file may hold limits for; NOTHING when no token
    could be compared.  ``logit_mse_rel``: the served logits' squared error
    against the reference's, at the kept columns, over the reference's
    mean square."""
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"max_logit_gap": float(flat.max()) if flat.size else NOTHING,
            "mean_logit_gap": float(flat.mean()) if flat.size else NOTHING,
            "off_argmax_share": float((flat > 0).mean()) if flat.size
            else NOTHING,
            "logit_mse_rel": err / ref2 if ref2 else NOTHING}


def compare(cell, weights, records, seed: int, window: Dict,
            readings: Optional[Dict] = None) -> Dict:
    """The numbers compared, each with its limit: ``{name: {value, limit}}``.
    ``readings``, when given, receives what the reference saw besides."""
    lim = cell.serving["check"]
    ref = cell.reference()
    conf = _reference_conf(cell)
    t0 = time.perf_counter()
    picked = sample(records, seed, int(lim["tokens"]))
    cols = columns(int(cell.config["vocab_size"]), seed)
    gaps, err, ref2 = [], 0.0, 0.0
    for r in picked:
        g, _, want = ref.logit_gaps(weights, conf, r.planned.prompt,
                                    r.tokens, cols)
        gaps.append(g)
        if len(r.rows) == len(r.tokens):
            got = np.stack(r.rows)
            err += float(np.sum((got - want) ** 2))
            ref2 += float(np.sum(want ** 2))
    gap = _numbers(gaps, err, ref2)
    seen = dict(gap, requests=len(picked), tokens=sum(len(g) for g in gaps),
                seconds=time.perf_counter() - t0)
    print(f"[bench] reference: {json.dumps(seen)}", file=sys.stderr)
    if readings is not None:
        readings.update(seen)
    done = [r for r in records
            if r.request is not None and not r.request.cancelled]
    max_seq = int(cell.serving["max_seq"])
    short = [r for r in done if len(r.tokens) < r.planned.max_new
             and len(r.planned.prompt) + len(r.tokens) < max_seq]
    mismatch = [r for r in done
                if list(r.tokens) != list(r.request.generated)]
    checks = {
        "requests_finished_none": {"value": int(not done), "limit": 0},
        "requests_short": {"value": len(short), "limit": 0},
        "stream_mismatch": {"value": len(mismatch), "limit": 0},
        "requests_failed": {"value": int(window["failed"]), "limit": 0},
    }
    for name, value in gap.items():
        if name in lim:
            checks[name] = {"value": value, "limit": float(lim[name])}
    return checks


def control(cell, weights, records, seed: int) -> Dict:
    """The control's numbers on the requests ``compare`` samples: the
    reference with its weights in int8 (one precision below the served
    bf16) put in the program's place.  At each position of the same
    prompts and served tokens, the token it puts first is read for its gap
    under the reference, and its logits at the kept columns for their
    error.  ``bench/control.py`` reads it on the chip; the benchmark's own
    runs never do."""
    lim = cell.serving["check"]
    ref = cell.reference()
    conf, low = _reference_conf(cell), _reference_conf(
        cell, weight_format="int8")
    picked = sample(records, seed, int(lim["tokens"]))
    cols = columns(int(cell.config["vocab_size"]), seed)
    gaps, err, ref2 = [], 0.0, 0.0
    for r in picked:
        _, first, got = ref.logit_gaps(weights, low, r.planned.prompt,
                                       r.tokens, cols)
        g, _, want = ref.logit_gaps(weights, conf, r.planned.prompt,
                                    r.tokens, cols, targets=first)
        gaps.append(g)
        err += float(np.sum((got - want) ** 2))
        ref2 += float(np.sum(want ** 2))
    return dict(_numbers(gaps, err, ref2), requests=len(picked),
                tokens=sum(len(g) for g in gaps))
