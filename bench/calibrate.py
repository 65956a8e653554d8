"""The readings a cell's output limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 --fault-seeds 4,5,6 [--out <file>]

In one process (set-up is long; the chip is held once):

- sound runs: for each seed, the program through its first ten
  aggregations and the reference after them, the numbers of
  ``bench.correct`` (the lower readings);
- the control: for each control seed, the reference computed in bfloat16
  in the program's place, against the float32 reference on the same
  arrivals (an upper reading);
- faults: each of ``bench.faults`` planted in the program, on each fault
  seed (the other upper readings).

The benchmark's own runs never run this. Each reading is printed as one
JSON line and all of them are written to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", default="half_batch,answer_altered")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax.numpy as jnp
    from bench import correct, reference

    bench = run.load_benchmark()
    cell = run.find_cell(bench, args.workload)
    none = {k: None for k in correct.NUMBERS}
    readings = []

    def emit(kind, seed, values):
        row = {"kind": kind, "seed": seed, "numbers": values}
        readings.append(row)
        print(json.dumps(row), flush=True)

    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        keep: dict = {}
        res = run.run_cell(cell, seed, 0.0, False, bench=bench, limits=none,
                           keep=keep)
        if seed in args.seeds:
            emit("sound", seed, {k: v["value"]
                                 for k, v in res["checks"].items()})
        if seed in args.control_seeds:
            ctl = reference.replay(keep["config"], keep["traffic"],
                                   keep["world"], keep["w0"],
                                   keep["record"].arrivals, keep["shuffle"],
                                   dtype=jnp.bfloat16)
            emit("control", seed, correct.numbers(
                ctl, keep["want"], keep["w0_flat"],
                reference.leaf_sizes(keep["config"])))
        keep.clear()
    for fault in [f for f in args.faults.split(",") if f]:
        for seed in args.fault_seeds:
            res = run.run_cell(cell, seed, 0.0, False, bench=bench,
                               limits=none, fault=fault)
            emit(fault, seed, {k: v["value"]
                               for k, v in res["checks"].items()})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"cell": args.workload, "readings": readings}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
