"""Readings that the limits of ``check`` are set from, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> \\
        --seeds <n> ... [--rate <r>]

One process.  For every seed it serves the cell as the benchmark does and
prints one JSON line with the numbers compared and what the reference saw
(``sound``: the lower readings), and the same numbers for the control on
the same requests (``control``: the upper readings): the reference with
int8 weights, one precision below the bf16 the configuration states, put
in the program's place (``check.control``).  ``control_correct`` says
whether the control would pass the cell's limits: it has to be false.
The benchmark's own runs never run the control.

The program's own int8 weight path cannot serve as the control at the
cell's size: it keeps the raw bf16 weights for prefill beside the int8
ones, and with the decode step's undonated cache that passes a v5e's
memory (``PERF.md``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="arrival rate in place of the traffic file's")
    opts = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import check, run
    from bench.spec import load_cell

    cell = load_cell(opts.workload, ROOT)
    if opts.rate is not None:
        cell = dataclasses.replace(cell, traffic=dict(
            cell.traffic, rate_per_s=opts.rate))
    devs = run.check_devices(jax, cell.chips)
    run.enable_compile_cache(jax)
    lim = cell.serving["check"]
    for seed in opts.seeds:
        seen = {}
        out = run.run_cell(cell, seed, opts.seconds, False,
                           t_start=time.perf_counter(), devs=devs,
                           keep=seen.update)
        t0 = time.perf_counter()
        ctrl = check.control(cell, seen.pop("weights"), seen["records"], seed)
        ctrl["seconds"] = time.perf_counter() - t0
        seen.clear()
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "checks": {k: v["value"] for k, v in out["checks"].items()},
            "sound": out["reference"], "control": ctrl,
            "control_correct": all(ctrl[k] <= float(lim[k])
                                   for k in ctrl if k in lim
                                   and k != "tokens"),
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
        }), flush=True)


if __name__ == "__main__":
    main()
