"""Offline near-duplicate self-join: ``JoinEngine.self_join`` back to back.

Set-up makes the configuration's corpus, puts its rows in the order of the
run's seed and runs one whole join, which compiles every program the
window's joins use.  Each join in the window starts from the host
collection: it prepares it (length sort, upload, bitmap words, and for the
indexed driver the postings CSR), lets the planner choose the driver, and
returns the pairs to the host.  The window closes at the end of the first
join that ends at or after ``--seconds``; ``join_s`` is the window's length
over the joins it completed.  Every join's pairs are compared with the
plain reference once the window has closed, and a compile inside the
window fails the run too (``compiles_in_window``): the warm join has to
have compiled every program the window runs.
"""

from __future__ import annotations

import gc
import time

from chipbench import compare, generate, reference
from chipbench.harness import Outcome, Record, memory_peak_bytes

SIM = "jaccard"


def _join(col, tau: float, b: int, tracer):
    """One join from the host collection -> (pairs, stats, plan, prepare
    seconds, join seconds)."""
    import jax
    from repro.core.engine import JoinEngine, prepare
    from repro.core.plan import JoinPlanner

    t0 = time.perf_counter()
    with tracer.span("bench.prepare"):
        prep = prepare(col)
        engine = JoinEngine(prep, SIM, tau, planner=JoinPlanner(b=b))
        plan = engine.plan
        built = [*prep.device_arrays(),
                 prep.bitmap_words(plan.b, plan.method, mix=plan.mix)]
        prep.length_window_int(SIM, tau)
        if plan.driver == "indexed":
            built += list(prep.postings(SIM, tau, plan.ell).device_arrays())
        jax.block_until_ready(built)
    t1 = time.perf_counter()
    builds = prep.build_counts()
    with tracer.span("bench.self_join"):
        pairs, stats = engine.self_join(return_stats=True)
    t2 = time.perf_counter()
    if prep.build_counts() != builds:
        raise RuntimeError(f"the join rebuilt a prepared artifact: "
                           f"{builds} -> {prep.build_counts()}")
    return pairs, stats, plan, t1 - t0, t2 - t0


def inputs(cell, seed: int, seconds: float):
    """The corpus of ``seed``, and the exact pairs' queries (none: the
    self-join)."""
    return generate.shuffle(generate.make_corpus(cell.config), seed), None


def run(ctx) -> Outcome:
    from repro.core.collection import Collection

    b, tau = width_and_tau(ctx.cell)
    corpus, _ = inputs(ctx.cell, ctx.seed, ctx.seconds)
    col = Collection(tokens=corpus.tokens, lengths=corpus.lengths)
    _, _, plan, _, warm_s = _join(col, tau, b, ctx.tracer)
    ctx.log(f"plan: {plan.driver} b={plan.b} method={plan.method} "
            f"compaction={plan.compaction} block={plan.block}; warm join "
            f"{warm_s:.3f} s")

    results, stats, prepare_s, join_s = [], [], [], []
    compiles0 = ctx.compiles.count
    with ctx.tracer.window():
        start = time.perf_counter()
        setup_s = start - ctx.t0
        while True:
            pairs, st, _, prep_s, one_s = _join(col, tau, b, ctx.tracer)
            results.append(pairs)
            stats.append(st)
            prepare_s.append(prep_s)
            join_s.append(one_s)
            if time.perf_counter() - start >= ctx.seconds:
                break
        window_s = time.perf_counter() - start
    compiles = ctx.compiles.count - compiles0
    peak = memory_peak_bytes(ctx.cell.chips)
    gc.collect()

    t = time.perf_counter()
    want = reference.DeviceReference(corpus.tokens, corpus.lengths,
                                     tau).pairs()
    ref_s = time.perf_counter() - t
    missing = extra = failed = 0
    for got in results:
        m, e = compare.missing_extra(got, want, corpus.num_sets)
        missing, extra, failed = missing + m, extra + e, failed + bool(m or e)
    joins = len(results)
    ctx.log(f"window: {joins} joins in {window_s:.3f} s, {compiles} "
            f"compiles inside it; pairs per join {len(results[0])}, "
            f"reference {len(want)} ({ref_s:.1f} s)")
    run_record = Record(b=plan.b, n_sets=corpus.num_sets, joins=joins,
                        join_stats=stats, prepare_s=prepare_s)
    return Outcome(
        metrics={"join_s": window_s / joins, "setup_s": setup_s},
        attempted=joins, failed=failed,
        checks={"missing_pairs": (missing, 0), "extra_pairs": (extra, 0),
                "compiles_in_window": (compiles, 0)},
        memory_peak_bytes=peak, run=run_record,
        notes={"driver": plan.driver, "join_seconds": join_s,
               "pairs_per_join": int(len(results[0])),
               "reference_pairs": int(len(want)),
               "reference_s": ref_s, "window_s": window_s})


def width_and_tau(cell):
    """The bitmap width and threshold the cell joins at."""
    return int(cell.traffic["b"]), float(cell.traffic["tau"])
