"""Find the knee of an open-loop cell: the highest arrival rate whose queue
does not grow through a window.  Run once when a cell is defined; the
cell's traffic file then fixes its rate at about four fifths of the knee.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 1.0 1.5 2.0 ...

One process; per rate it serves the cell's traffic with ``rate_per_s``
replaced and prints one JSON line: tokens/s, TTFT percentiles, the TTFT
median of the window's first and last thirds, and the requests due but not
yet given a first token when the window closed (the backlog).  A queue
that grows shows as a last third far slower than the first and a backlog
that rises with the rate.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    opts = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import drive, run
    from bench.spec import load_cell

    cell = load_cell(opts.workload, ROOT)
    devs = run.check_devices(jax, cell.chips)
    run.enable_compile_cache(jax)
    for rate in opts.rates:
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_per_s=rate))
        seen = {}
        out = run.run_cell(c, opts.seed, opts.seconds, False,
                           t_start=time.perf_counter(), devs=devs,
                           keep=seen.update)
        t0, t1 = seen["due_open"], seen["due_close"]
        third = (t1 - t0) / 3
        due = [r for r in seen["records"] if t0 <= r.due < t1]
        ttft = lambda rs: [(r.times[0] if r.times and r.times[0] < t1
                            else t1) - r.due for r in rs]
        first = ttft([r for r in due if r.due < t0 + third])
        last = ttft([r for r in due if r.due >= t1 - third])
        backlog = sum(1 for r in due if not r.times or r.times[0] >= t1)
        m = out["metrics"]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "backlog": backlog,
            "tokens_per_s": m["tokens_per_s"]["value"],
            "ttft_p90_s": drive.ttft_p90_s(seen),
            "ttft_p50_first_third_s": float(np.median(first)) if first else None,
            "ttft_p50_last_third_s": float(np.median(last)) if last else None,
            "itl_p95_ms": m["itl_p95_ms"]["value"],
            "correct": out["correct"],
        }), flush=True)


if __name__ == "__main__":
    main()
