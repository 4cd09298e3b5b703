"""Compile and run time of bitmap generation on the default JAX backend.

Times ``repro.core.bitmap.generate_bitmaps`` of whichever ``repro`` tree is
on ``PYTHONPATH`` over a DBLP-like collection, so two trees compare on one
machine:

    PYTHONPATH=src python -m benchmarks.time_bitmap_generation
    PYTHONPATH=<other tree>/src python -m benchmarks.time_bitmap_generation

The persistent compile cache is off, so ``compile_s`` is a real compile.
Prints one JSON line per (b, method).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core import bitmap as bm
from repro.data.collections import dblp_like_collection


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--b", type=int, nargs="+", default=[512])
    ap.add_argument("--method", nargs="+", default=["set"])
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)

    jax.config.update("jax_enable_compilation_cache", False)
    col = dblp_like_collection(args.n, seed=0)
    tokens = jax.device_put(col.tokens)
    lengths = jax.device_put(col.lengths)
    dev = jax.devices()[0]
    for b in args.b:
        for method in args.method:
            fn = jax.jit(lambda t, n: bm.generate_bitmaps(t, n, b,
                                                          method=method))
            t0 = time.perf_counter()
            compiled = fn.lower(tokens, lengths).compile()
            compile_s = time.perf_counter() - t0
            compiled(tokens, lengths).block_until_ready()
            runs = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                compiled(tokens, lengths).block_until_ready()
                runs.append(time.perf_counter() - t0)
            print(json.dumps({
                "platform": dev.platform, "kind": dev.device_kind,
                "n": col.num_sets, "max_len": col.max_len, "b": b,
                "method": method, "compile_s": compile_s,
                "run_median_s": float(np.median(runs)),
                "run_min_s": min(runs), "reps": args.reps}), flush=True)


if __name__ == "__main__":
    main()
