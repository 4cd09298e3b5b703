"""Run time of the two forms of the blocked step's tile compaction on the
default JAX backend.

Times ``jnp.nonzero(mask, size=cap, fill_value=0)`` (the scatter form)
against ``repro.kernels.compaction``'s lookup form (running count, then
one binary search per slot) with each ``jnp.searchsorted`` loop, over one
random bool tile per case, and checks that every form returns the same
indices:

    PYTHONPATH=src python -m benchmarks.time_compaction_forms

A case is a density and a capacity; the default cases are the blocked
join's sparse tiles (0.02% of a 4096x4096 tile, cap 4096 and 2^19) and,
for the crossover, tiles whose true count fills 80% of caps 2^18 to 2^23.
Prints one JSON line per case.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import compaction

FORMS = {
    "scatter": lambda m, cap: jnp.nonzero(m, size=cap, fill_value=0),
    "lookup_scan": lambda m, cap: compaction._lookup_form(m, cap, "scan"),
    "lookup_scan_unrolled": lambda m, cap: compaction._lookup_form(
        m, cap, "scan_unrolled"),
}


def _cases(tile: int):
    area = tile * tile
    yield 0.0002, 4096
    yield 0.0002, 1 << 19
    for log_cap in range(18, 24):
        yield 0.8 * (1 << log_cap) / area, 1 << log_cap


def _time(fn, reps: int) -> float:
    jax.block_until_ready(fn())
    runs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        runs.append(time.perf_counter() - t0)
    return float(np.median(runs))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tile", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    shape = (args.tile, args.tile)
    for i, (density, cap) in enumerate(_cases(args.tile)):
        mask = jax.random.bernoulli(jax.random.key(i), density, shape)
        line = {"platform": dev.platform, "kind": dev.device_kind,
                "tile": args.tile, "density": density, "cap": cap,
                "count": int(mask.sum()),
                "rule": "lookup" if compaction.lookup_applies(
                    mask.size, cap) else "scatter"}
        want = None
        for name, form in FORMS.items():
            fn = jax.jit(form, static_argnums=1)
            got = jax.device_get(fn(mask, cap))
            if want is None:
                want = got
            elif not all(np.array_equal(g, w) for g, w in zip(got, want)):
                raise AssertionError(f"{name} differs from scatter at {line}")
            line[f"{name}_s"] = _time(lambda: fn(mask, cap), args.reps)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
