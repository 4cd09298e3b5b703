#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout, loads the cell's
configuration, traffic and metric files (see ``chipbench/harness.py``),
sets up, measures for ``--seconds`` and checks every answer against the
plain reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end metrics, or
with ``--trace 1`` the per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared, with its limit,
which are also the last lines on standard error.

There is no CPU mode: without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
# The persistent compilation cache lives at one fixed path in the checkout
# unless the environment names one; the TPU runtime's logs stay in the
# checkout too (its default is a fixed directory under /tmp).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "chipbench_out",
                                                  "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    from repro import compile_cache

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = harness.resolve(spec, args.workload, ROOT)
    compile_cache.enable()
    import jax
    # Cache every program, however quick to compile, so that a checkout's
    # second run compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out_dir = os.path.join(ROOT, "chipbench_out", args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), out_dir=out_dir, t0=T0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
