#!/usr/bin/env python3
"""Record the small chip trace the benchmark's stage tests read.

    python3 scripts/record_scoped_trace.py [--out chiprun_out/scoped.xplane.pb.gz]

On one TPU: a DBLP-shaped corpus of 4,096 sets (``chipbench/configs/
dblp-dedup.json`` at that size), one blocked self-join (Jaccard 0.5, b 512,
device compaction) and one indexed self-join (Jaccard 0.9, b 128), each
from the host collection and with 1,024-row blocks and probe chunks so
that the driver loops run several steps.  Both joins run once to compile,
then once more inside the benchmark's ``bench.window`` span under the
profiler; the gzipped ``.xplane.pb`` goes to ``--out``.  Exits non-zero
without a TPU.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

N_SETS = 4096
BLOCK = 1024
JOINS = (("blocked", 0.5, 512), ("indexed", 0.9, 128))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "scoped.xplane.pb.gz"))
    args = ap.parse_args(argv)

    import jax

    from chipbench import generate, tracing
    from repro.core.collection import Collection
    from repro.core.engine import JoinEngine
    from repro.core.plan import JoinPlanner

    if jax.default_backend() != "tpu":
        raise SystemExit("record_scoped_trace: needs a TPU")
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "dblp-dedup.json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg["n_sets"] = N_SETS
    cfg["planted"]["clusters"] = N_SETS // 100
    corpus = generate.make_corpus(cfg)
    col = Collection(tokens=corpus.tokens, lengths=corpus.lengths)

    def join_all():
        for driver, tau, b in JOINS:
            plan = JoinPlanner(b=b).plan("jaccard", tau, n_r=N_SETS)
            plan = dataclasses.replace(plan, driver=driver, block=BLOCK,
                                       compaction="device")
            engine = JoinEngine(col, "jaccard", tau, plan=plan)
            pairs, stats = engine.self_join(return_stats=True)
            print(f"{driver} tau={tau} b={b}: {len(pairs)} pairs, "
                  f"{stats.blocks_total} steps, {stats.slots} slots",
                  flush=True)

    join_all()
    tmp = tempfile.mkdtemp()
    tracer = tracing.Tracer(True, tmp)
    with tracer.window():
        join_all()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(tracer.path, "rb") as src, gzip.open(args.out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"out": args.out, "bytes": os.path.getsize(args.out),
                      "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
