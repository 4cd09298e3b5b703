"""Benchmark driver — one module per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--smoke] [--json PATH]
                                               [--trajectory[=PATH]]
                                               [module-substring ...]
Prints ``name,us_per_call,derived`` CSV rows.

``--smoke`` runs a fast subset (and tells modules that honour
``REPRO_BENCH_SMOKE`` to shrink their collections) — used by
``scripts/check.sh`` as a does-the-benchmark-stack-still-run gate.

``--json PATH`` additionally writes every row (including any attached
``JoinStats`` dict — counters, filter_ratio, precision, overflow_blocks) to
PATH as a JSON list, so perf/filter-ratio trajectories can be diffed across
PRs instead of eyeballing CSV.

``--trajectory[=PATH]`` *appends* one summary entry (timestamp, git
revision, row list with stats) to the JSON list at PATH — the cross-PR perf
trajectory that ``benchmarks/perf_gate.py`` gates on.  An explicit path must
use the ``--trajectory=PATH`` form; bare ``--trajectory`` (or an empty
``--trajectory=``) resolves to the newest repo-root ``BENCH_PR*.json``
(:func:`default_trajectory`), and any following tokens are ordinary module
filters — bare ``--trajectory bench_engine`` filters to the engine bench
rather than writing a file named ``bench_engine``.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import subprocess
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODULES = [
    "benchmarks.bench_expected_bounds",    # Fig. 5 / Eq. 4-6
    "benchmarks.bench_cutoffs",            # Fig. 6-7 / Alg. 6
    "benchmarks.bench_cpu_algos",          # Tables 5-8
    "benchmarks.bench_filter_ratio",       # Table 9
    "benchmarks.bench_generation_methods", # Fig. 10
    "benchmarks.bench_precision",          # Fig. 11
    "benchmarks.bench_device_join",        # Table 10
    "benchmarks.bench_rs_join",            # R×S vs self-join
    "benchmarks.bench_engine",             # prepared-vs-rebuild amortization
    "benchmarks.bench_kernels",            # kernel rooflines (perf gate rows)
    "benchmarks.bench_serve",              # online serving (coalesced probes)
    "benchmarks.bench_store",              # appendable corpus store (LSM)
]

SMOKE_MODULES = [
    "benchmarks.bench_expected_bounds",
    "benchmarks.bench_rs_join",
    "benchmarks.bench_engine",
    "benchmarks.bench_kernels",
    "benchmarks.bench_serve",
    "benchmarks.bench_store",
]


def default_trajectory() -> str:
    """Newest repo-root ``BENCH_PR*.json`` — so neither this file nor
    ``check.sh`` needs a manual path bump every PR.  A repo with no
    trajectory yet starts one at ``BENCH_PR0.json``."""
    found = []
    for p in glob.glob(os.path.join(_REPO_ROOT, "BENCH_PR*.json")):
        m = re.fullmatch(r"BENCH_PR(\d+)\.json", os.path.basename(p))
        if m:
            found.append((int(m.group(1)), p))
    if found:
        return max(found)[1]
    return os.path.join(_REPO_ROOT, "BENCH_PR0.json")


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def append_trajectory(path: str, rows, *, smoke: bool) -> int:
    """Append one run summary to the JSON trajectory list at ``path``.

    The file holds a list of entries ``{ts, rev, smoke, rows}``.  A corrupt
    or non-list file is moved aside to ``path + '.corrupt'`` (with a warning)
    and a fresh history started — never silently deleted: the trajectory is
    the cross-PR perf history the regression gate runs on.  Returns the new
    length.
    """
    history = []
    if os.path.exists(path):
        corrupt = None
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, list):
                history = loaded
            else:
                corrupt = f"not a list ({type(loaded).__name__})"
        except (json.JSONDecodeError, OSError) as e:
            corrupt = str(e)
        if corrupt is not None:
            aside = path + ".corrupt"
            os.replace(path, aside)
            print(f"# WARNING: trajectory {path} unreadable ({corrupt}); "
                  f"moved aside to {aside}, starting fresh history",
                  file=sys.stderr)
    history.append({
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "rev": _git_rev(),
        "smoke": smoke,
        "rows": [r.to_json() for r in rows],
    })
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(history, f, indent=1)
    os.replace(tmp, path)
    return len(history)


@dataclasses.dataclass
class Args:
    smoke: bool = False
    json_path: str | None = None
    trajectory_path: str | None = None
    filters: list[str] = dataclasses.field(default_factory=list)


def parse_args(argv: list[str]) -> Args:
    """CLI parsing, extracted so the ``--trajectory`` forms are testable.

    ``--trajectory`` never consumes the next token: explicit paths must use
    ``--trajectory=PATH`` (empty value → default), so following non-flag
    tokens always act as module filters.
    """
    args = Args()
    rest = []
    it = iter(argv)
    for a in it:
        if a == "--smoke":
            args.smoke = True
        elif a == "--json":
            try:
                args.json_path = next(it)
            except StopIteration:
                raise SystemExit("--json needs a path argument")
        elif a.startswith("--json="):
            args.json_path = a.split("=", 1)[1]
        elif a == "--trajectory":
            args.trajectory_path = default_trajectory()
        elif a.startswith("--trajectory="):
            args.trajectory_path = a.split("=", 1)[1] or default_trajectory()
        elif a.startswith("-"):
            raise SystemExit(f"unknown flag {a!r}")
        else:
            rest.append(a)
    args.filters = rest
    return args


def main() -> None:
    import importlib

    from repro import compile_cache

    compile_cache.enable()
    args = parse_args(sys.argv[1:])
    modules = SMOKE_MODULES if args.smoke and not args.filters else MODULES
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    print("name,us_per_call,derived")
    t_all = time.time()
    all_rows = []
    for modname in modules:
        if args.filters and not any(f in modname for f in args.filters):
            continue
        t0 = time.time()
        mod = importlib.import_module(modname)
        for row in mod.run():
            all_rows.append(row)
            print(row.csv(), flush=True)
        print(f"# {modname} done in {time.time()-t0:.1f}s", flush=True)
    print(f"# total {time.time()-t_all:.1f}s")
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump([r.to_json() for r in all_rows], f, indent=1)
        print(f"# wrote {len(all_rows)} rows to {args.json_path}")
    if args.trajectory_path:
        n = append_trajectory(args.trajectory_path, all_rows, smoke=args.smoke)
        print(f"# appended trajectory entry {n} to {args.trajectory_path}")


if __name__ == "__main__":
    main()
