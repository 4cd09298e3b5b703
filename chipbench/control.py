#!/usr/bin/env python3
"""Readings of the control: the plain reference put in the program's place
with its exact verification skipped, so that every pair whose b-bit bitmap
bound reaches the threshold is reported (``reference.control_pairs``).

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <run_seconds>

For each seed it makes the inputs a run of the cell makes, compares the
control's pairs with the reference's exactly as a run compares the
program's, and prints one JSON line per seed with the numbers compared.
The benchmark's runs never run it; its readings set the upper end of each
limit (see PERF.md).  Runs on whatever device JAX finds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def readings(cell, seed: int, seconds: float) -> dict:
    from chipbench import compare, reference

    entry = cell.entry()
    corpus, queries = entry.inputs(cell, seed, seconds)
    b, tau = entry.width_and_tau(cell)
    ref = reference.DeviceReference(corpus.tokens, corpus.lengths, tau)
    q = () if queries is None else queries[1:]
    t = time.perf_counter()
    want = ref.pairs(*q)
    ctl = ref.control_pairs(b, *q)
    width = corpus.num_sets if queries is None else len(queries[2])
    missing, extra = compare.missing_extra(ctl, want, width)
    return {"workload": cell.name, "seed": seed, "reference_pairs": len(want),
            "control_pairs": len(ctl), "missing_pairs": missing,
            "extra_pairs": extra, "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.resolve(json.load(f), args.workload, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
