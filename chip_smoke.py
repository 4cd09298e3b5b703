#!/usr/bin/env python3
"""Chip smoke: drive the join engine, the probe service and the corpus store
once on a TPU, through the entry points a user calls, and check every answer
against an independent host reference.

    python3 chip_smoke.py                 # one chip, phases (a)-(e)
    python3 chip_smoke.py --four-chips    # sharded-indexed + ring on 4 chips

The corpus is ``dblp_like_collection`` (the paper's §5 DBLP shape: mean set
size ~106 over 3801 tokens) with near-duplicate clusters planted by
``with_duplicates``, generated from ``--seed``.

Phases (one chip):

* (a) offline self-join at Jaccard 0.9 through ``JoinEngine``; the planner
  must pick ``indexed``.  Run at b=128 and b=512.
* (b) self-join at Jaccard 0.5; the planner must pick ``blocked`` with
  device compaction (the dense SWAR kernels).
* (c) an R×S probe batch through ``engine.probe`` against the resident
  corpus.
* (d) a ``JoinSession`` over the small corpus: ``warm_buckets`` on a
  sample of requests, then single-set ``submit``/``poll`` requests the
  sample did not include, each bit-identical to ``engine.probe``, with no
  trace after warm-up.
* (e) a ``CorpusStore`` over the small corpus: appends, probe,
  ``compact()``, probe; the pairs before and after compaction are
  identical.

Exactness has no tolerance: sampled rows of the full-size joins and every
pair of a small corpus are compared with a host numpy reference (inverted
index + ``np.bincount`` overlaps, float64 threshold).  Every time printed
is smoke set-up, not a benchmark number: ``compile`` sums XLA's backend
compile durations inside the call, ``run`` is the rest of its wall time.
Any failure raises; the last stdout line is the JSON result, printed only
when every phase passed on a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import join as join_mod  # noqa: E402
from repro.core.collection import Collection, from_lists  # noqa: E402
from repro.core.constants import PAD_TOKEN  # noqa: E402
from repro.core.engine import JoinEngine  # noqa: E402
from repro.core.plan import JoinPlanner  # noqa: E402
from repro.data.collections import dblp_like_collection, with_duplicates  # noqa: E402
from repro.index import candidates as cands  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.serve import JoinSession  # noqa: E402
from repro.store import CorpusStore  # noqa: E402

SIM = "jaccard"
TAU_INDEXED = 0.9
TAU_BLOCKED = 0.5
# At b=128 the Alg. 7 cutoff for tau=0.5 is 95 tokens, below the DBLP-like
# mean set size, so the filter keeps ~95% of window pairs; b=512 prunes.
B_BLOCKED = 512

N_SETS = 100_000        # phases (a)-(c): the paper's smallest collection
SMALL_SETS = 8192       # whole-reference checks, phases (d) and (e)
SAMPLE_ROWS = 256       # rows checked against the reference per join
BATCH_ROWS = 4096       # phase (c) probe batch
WARM_REQUESTS = 256     # phase (d) warm-up sample
REQUESTS = 300          # phase (d) measured requests
APPEND_ROWS = 512       # phase (e) rows per append
# Four chips: at 8192 sets most probe chunks stay under the indexed
# driver's 2^22-slot ceiling, so the sharded step, not its dense fallback,
# does the work (DBLP-like prefixes expand ~1.1e4 postings per probe at
# 10^5 sets, and expansion grows with N).
FOUR_CHIP_SETS = 8192


def log(msg: str) -> None:
    print(msg, flush=True)


class Failure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failure(what)


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

def make_corpus(n_sets: int, seed: int) -> Collection:
    """DBLP-like sets plus planted near-duplicate clusters, ``n_sets`` rows."""
    n_clusters = max(n_sets // 100, 1)
    cluster_size = 3
    base = dblp_like_collection(n_sets - n_clusters * (cluster_size - 1),
                                seed=seed)
    return with_duplicates(base, n_clusters=n_clusters,
                           cluster_size=cluster_size, jaccard=0.95,
                           seed=seed + 1)


def near_copies(col: Collection, n: int, seed: int) -> Collection:
    """``n`` probes: copies of random corpus rows with one token swapped for
    a token the corpus never uses (Jaccard ~0.98 to their source row)."""
    rng = np.random.default_rng(seed)
    fresh = int(col.tokens[col.tokens != PAD_TOKEN].max()) + 1
    rows = []
    for k, i in enumerate(rng.integers(0, col.num_sets, size=n)):
        r = col.row(int(i)).tolist()
        r.pop(int(rng.integers(0, len(r))))
        rows.append(r + [fresh + k])
    return from_lists(rows)


# ---------------------------------------------------------------------------
# Independent host reference
# ---------------------------------------------------------------------------

class Reference:
    """Exact overlaps of any token set against a whole collection: an
    inverted index (token -> rows) and one ``np.bincount`` per query, with
    the float64 Jaccard threshold ``o >= tau / (1 + tau) * (|r| + |s|)``."""

    def __init__(self, col: Collection):
        self.n = col.num_sets
        self.lengths = col.lengths.astype(np.int64)
        live = np.arange(col.max_len)[None, :] < col.lengths[:, None]
        toks = col.tokens[live]
        rows = np.repeat(np.arange(self.n), col.lengths)
        order = np.argsort(toks, kind="stable")
        self.rows = rows[order]
        self.vocab, self.starts = np.unique(toks[order], return_index=True)
        self.ends = np.append(self.starts[1:], len(order))

    def partners(self, query: np.ndarray, tau: float) -> np.ndarray:
        k = np.searchsorted(self.vocab, query)
        inside = k < len(self.vocab)
        k = k[inside][self.vocab[k[inside]] == query[inside]]
        hits = [self.rows[self.starts[t]:self.ends[t]] for t in k]
        o = np.bincount(np.concatenate(hits) if hits else np.zeros(0, int),
                        minlength=self.n)
        need = tau / (1.0 + tau) * (len(query) + self.lengths)
        ok = (o >= need) & (self.lengths > 0) & (len(query) > 0)
        return np.nonzero(ok)[0]


def partner_lists(pairs: np.ndarray, side: int):
    """row -> sorted partner ids, for rows on ``side`` of ``pairs`` (and on
    both sides when the pairs are a self-join's, see ``self_join``)."""
    out: dict = {}
    for a, b in pairs[:, [side, 1 - side]]:
        out.setdefault(int(a), []).append(int(b))
    return out


def check_self_join_rows(pairs, col: Collection, ref: Reference, tau: float,
                         n_rows: int, rng) -> int:
    """Compare each sampled row's partners with the reference; half the
    sample is uniform, half is drawn from rows the join reported."""
    both = np.concatenate([pairs, pairs[:, ::-1]]) if len(pairs) else pairs
    got = partner_lists(both, 0) if len(pairs) else {}
    uniform = rng.choice(col.num_sets, size=n_rows // 2, replace=False)
    reported = np.array(sorted(got), dtype=np.int64)
    hit = (rng.choice(reported, size=min(n_rows - len(uniform), len(reported)),
                      replace=False) if len(reported) else reported)
    sample = np.unique(np.concatenate([uniform, hit]))
    for i in sample:
        want = ref.partners(col.row(int(i)), tau)
        want = want[want != i]
        check(np.array_equal(np.array(sorted(got.get(int(i), [])),
                                      dtype=np.int64), want),
              f"row {i}: partners differ from the reference")
    return len(sample)


def check_probe_rows(pairs, batch: Collection, ref: Reference, tau: float,
                     n_rows: int, rng) -> int:
    got = partner_lists(pairs, 1) if len(pairs) else {}
    sample = rng.choice(batch.num_sets, size=min(n_rows, batch.num_sets),
                        replace=False)
    for q in sample:
        want = ref.partners(batch.row(int(q)), tau)
        check(np.array_equal(np.array(sorted(got.get(int(q), [])),
                                      dtype=np.int64), want),
              f"probe row {q}: partners differ from the reference")
    return len(sample)


def whole_self_join(col: Collection, tau: float) -> np.ndarray:
    """Every pair (i < j) of a small collection, from a sparse one-hot
    overlap product."""
    import scipy.sparse as sp

    ref = Reference(col)
    x = sp.csr_matrix((np.ones(len(ref.rows), np.float32),
                       (ref.rows, np.repeat(np.arange(len(ref.vocab)),
                                            ref.ends - ref.starts))),
                      shape=(col.num_sets, len(ref.vocab)))
    o = (x @ x.T).toarray()
    lens = ref.lengths
    need = tau / (1.0 + tau) * (lens[:, None] + lens[None, :])
    ok = (o >= need) & (lens[:, None] > 0) & (lens[None, :] > 0)
    i, j = np.nonzero(np.triu(ok, k=1))
    return np.stack([i, j], axis=1).astype(np.int64)


# ---------------------------------------------------------------------------
# Checks shared by every phase
# ---------------------------------------------------------------------------

def resolved_impls(plan) -> dict:
    impls = {
        "dense": kops.resolve_impl(plan.impl, plan.b),
        "pair_verdict": kops._resolve_pairwise_impl(plan.impl, plan.b),
        "entry_filter": kops._resolve_entry_impl(plan.impl),
    }
    check("ref" not in impls.values(), f"a reference impl on the chip: {impls}")
    check(kops._on_tpu(), "kernels would run in interpret mode")
    return impls


def check_funnel(stats) -> None:
    check(stats.candidates_generated >= stats.candidates >= stats.verified_true,
          f"funnel out of order: {stats}")


def check_no_fallback(engine) -> None:
    check(not engine.fallbacks, f"engine fell back: {engine.fallbacks}")


def device_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use', 'n/a')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")


def report(phase: str, plan, n: int, stats, wall: float, compile_s: float,
           extra: str = "") -> None:
    log(f"[{phase}] {plan.describe()}")
    log(f"[{phase}] impls={resolved_impls(plan)} interpret=False N={n} "
        f"b={plan.b} {device_bytes()}")
    log(f"[{phase}] smoke set-up seconds (not a benchmark): "
        f"compile={compile_s:.3f} run={wall - compile_s:.3f}")
    log(f"[{phase}] funnel postings_expanded={stats.postings_expanded} "
        f"candidates_generated={stats.candidates_generated} "
        f"candidates={stats.candidates} verified_true={stats.verified_true} "
        f"overflow_blocks={stats.overflow_blocks} {extra}")


_COMPILE_S = [0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def timed(fn):
    """Run ``fn`` -> (result, wall seconds, of which XLA compile seconds)."""
    c0 = _COMPILE_S[0]
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, _COMPILE_S[0] - c0


def hlo_has_kernel(compiled_text: str, what: str) -> None:
    check("tpu_custom_call" in compiled_text,
          f"{what}: no Pallas kernel (tpu_custom_call) in the compiled HLO")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_indexed(corpus, ref, small, rng, b: int):
    planner = JoinPlanner(b=b)
    engine = JoinEngine(corpus, SIM, TAU_INDEXED, planner=planner)
    check(engine.plan.driver == "indexed",
          f"planner picked {engine.plan.driver}, not indexed")
    (pairs, stats), wall, comp = timed(
        lambda: engine.self_join(return_stats=True))
    report(f"a b={b}", engine.plan, corpus.num_sets, stats, wall, comp,
           f"pairs={len(pairs)}")
    check_funnel(stats)
    n_checked = check_self_join_rows(pairs, corpus, ref, TAU_INDEXED,
                                     SAMPLE_ROWS, rng)
    args_spec, statics = cands.chunk_step_spec(
        engine.prepared, sim=SIM, tau=TAU_INDEXED, b=b,
        method=engine.plan.method, probe_block=engine.plan.block)
    hlo_has_kernel(cands._indexed_chunk_step.lower(*args_spec, **statics)
                   .compile().as_text(), "indexed chunk step")
    check_no_fallback(engine)
    log(f"[a b={b}] exact: {n_checked} sampled rows equal the reference; "
        f"chunk step HLO has tpu_custom_call; no engine fallback")

    small_engine = JoinEngine(small, SIM, TAU_INDEXED, planner=planner)
    check(small_engine.plan.driver == "indexed",
          f"small corpus: planner picked {small_engine.plan.driver}")
    (sp, sst), wall, comp = timed(
        lambda: small_engine.self_join(return_stats=True))
    report(f"a b={b} whole", small_engine.plan, small.num_sets, sst, wall,
           comp, f"pairs={len(sp)}")
    check(np.array_equal(sp, whole_self_join(small, TAU_INDEXED)),
          "small corpus: indexed pairs differ from the whole reference")
    check_funnel(sst)
    check_no_fallback(small_engine)
    log(f"[a b={b} whole] exact: every pair equals the reference")
    return engine


def phase_blocked(corpus, ref, small, rng):
    planner = JoinPlanner(b=B_BLOCKED)
    engine = JoinEngine(corpus, SIM, TAU_BLOCKED, planner=planner)
    plan = engine.plan
    check(plan.driver == "blocked" and plan.compaction == "device",
          f"planner picked {plan.driver}/{plan.compaction}, not "
          f"blocked/device")
    (pairs, stats), wall, comp = timed(
        lambda: engine.self_join(return_stats=True))
    report("b", plan, corpus.num_sets, stats, wall, comp,
           f"pairs={len(pairs)}")
    check_funnel(stats)
    n_checked = check_self_join_rows(pairs, corpus, ref, TAU_BLOCKED,
                                     SAMPLE_ROWS, rng)
    hlo_has_kernel(blocked_step_hlo(engine), "blocked step")
    check_no_fallback(engine)
    log(f"[b] exact: {n_checked} sampled rows equal the reference; "
        f"block step HLO has tpu_custom_call; no engine fallback")

    small_engine = JoinEngine(small, SIM, TAU_BLOCKED, planner=planner)
    check(small_engine.plan.driver == "blocked",
          f"small corpus: planner picked {small_engine.plan.driver}")
    (sp, sst), wall, comp = timed(
        lambda: small_engine.self_join(return_stats=True))
    report("b whole", small_engine.plan, small.num_sets, sst, wall, comp,
           f"pairs={len(sp)}")
    check(np.array_equal(sp, whole_self_join(small, TAU_BLOCKED)),
          "small corpus: blocked pairs differ from the whole reference")
    check_funnel(sst)
    check_no_fallback(small_engine)
    log("[b whole] exact: every pair equals the reference")


def blocked_step_hlo(engine) -> str:
    """Compiled HLO of the blocked driver's fused block step at the plan's
    block size (the first diagonal block pair)."""
    from repro.core import bitmap as bm
    from repro.core import expected, verify

    plan, prep = engine.plan, engine.prepared
    blk = min(plan.block, prep.num_sets)
    tokens, lengths = prep.device_arrays()
    words = prep.bitmap_words(plan.b, plan.method, mix=plan.mix)
    _, _, lo, hi = prep.length_window_int(SIM, engine.tau)
    need_tab = verify.min_overlap_table_dev(SIM, engine.tau, prep.max_len,
                                            prep.max_len)
    cutoff = expected.cutoff_point(bm.choose_method(engine.tau, plan.b),
                                   plan.b, engine.tau)
    s = slice(0, blk)
    return join_mod._resident_block_step.lower(
        tokens[s], lengths[s], words[s], tokens[s], lengths[s], words[s],
        lo[s], hi[s], need_tab, np.int32(0), np.int32(0),
        sim=SIM, tau=engine.tau, cap=128, diag=True, cutoff=int(cutoff),
        impl=plan.impl).compile().as_text()


def phase_probe(engine, corpus, ref, args, rng):
    batch = near_copies(corpus, BATCH_ROWS, seed=args.seed + 7)
    (pairs, stats), wall, comp = timed(
        lambda: engine.probe(batch, return_stats=True))
    report("c", engine.plan, corpus.num_sets, stats, wall, comp,
           f"batch_rows={batch.num_sets} pairs={len(pairs)}")
    check(len(pairs) > 0, "probe found no pair for its near-copies")
    check_funnel(stats)
    n_checked = check_probe_rows(pairs, batch, ref, TAU_INDEXED,
                                 SAMPLE_ROWS, rng)
    check_no_fallback(engine)
    log(f"[c] exact: {n_checked} sampled probe rows equal the reference; "
        f"no engine fallback")


def phase_session(corpus, args):
    # A batch flushes when max_batch rows are queued (no wait deadline).
    sess = JoinSession(corpus, SIM, TAU_INDEXED, max_batch=64,
                       max_wait=float("inf"), planner=JoinPlanner(b=128))
    check(sess.plan.driver == "indexed", "session plan is not indexed")

    def single_sets(n, seed):
        probes = near_copies(corpus, n, seed=seed)
        return [from_lists([probes.row(i).tolist()])
                for i in range(probes.num_sets)]

    sample = single_sets(WARM_REQUESTS, args.seed + 12)
    requests = single_sets(REQUESTS, args.seed + 11)
    n_warm, warm_s, warm_c = timed(lambda: sess.warm_buckets(sample))
    warm_traces = sess.entrypoints.stats()["traces"]
    before = sess.stats_summary()
    log(f"[d] {sess.plan.describe()}")
    log(f"[d] impls={resolved_impls(sess.plan)} interpret=False "
        f"N={corpus.num_sets} b={sess.plan.b} {device_bytes()}")
    log(f"[d] smoke set-up seconds (not a benchmark): warm_buckets on "
        f"{len(sample)} sample requests compile={warm_c:.3f} "
        f"run={warm_s - warm_c:.3f} ({n_warm} entrypoint traces, row "
        f"bucket -> (width, prefix width, capacity): {sess._shape_hints})")

    def serve():
        out = []
        for req in requests:
            out.append(sess.submit(req))
            sess.poll()
        sess.flush()
        return out

    tickets, serve_s, serve_c = timed(serve)
    traced = dict(sess.entrypoints.trace_counts)
    traces = sess.entrypoints.stats()["traces"] - warm_traces
    after = sess.stats_summary()
    moved = {k: after[k] - before[k] for k in (
        "coalesced_requests", "sequential_requests", "coalesced_batches")}
    log(f"[d] smoke set-up seconds (not a benchmark): {len(requests)} "
        f"requests compile={serve_c:.3f} run={serve_s - serve_c:.3f}; "
        f"{moved} traces_after_warmup={traces}")
    check(all(t.done for t in tickets), "a request was never answered")
    check(traces == 0, f"{traces} traces after warm-up; traced entrypoint "
          f"keys (key: traces): {traced}")
    oracle = JoinEngine(sess.prepared, SIM, TAU_INDEXED, plan=sess.plan)
    n_pairs = 0
    for t, req in zip(tickets, requests):
        pairs, stats = t.result()
        want_pairs, want_stats = oracle.probe(req, return_stats=True)
        check(np.array_equal(pairs, want_pairs)
              and stats.to_dict() == want_stats.to_dict(),
              f"session request {t.seq} differs from engine.probe")
        n_pairs += len(pairs)
    check(n_pairs > 0, "session found no pair for its near-copies")
    check_no_fallback(sess.engine)
    check_no_fallback(oracle)
    log(f"[d] exact: {len(requests)} requests bit-identical to "
        f"engine.probe (pairs and JoinStats), pairs={n_pairs}")


def phase_store(corpus, args, rng):
    store = CorpusStore(corpus, SIM, TAU_INDEXED, planner=JoinPlanner(b=128))
    check(store.plan.driver == "indexed", "store plan is not indexed")
    for k in range(3):
        store.append(near_copies(corpus, APPEND_ROWS,
                                 seed=args.seed + 20 + k), compact=False)
    batch = near_copies(corpus, BATCH_ROWS // 4, seed=args.seed + 30)
    (before, st_before), wall, comp = timed(
        lambda: store.probe(batch, return_stats=True))
    engines = [seg.engine(store) for seg in store.segments()]
    check(len(store.deltas) == 3, "appends were folded early")
    full = store.collection()
    ref = Reference(full)
    n_checked = check_probe_rows(before, batch, ref, TAU_INDEXED,
                                 SAMPLE_ROWS // 2, rng)
    _, compact_s, _ = timed(store.compact)
    check(not store.deltas and store.compactions == 1, "compaction did not run")
    after, st_after = store.probe(batch, return_stats=True)
    check(np.array_equal(before, after),
          "pairs differ across compaction")
    check_funnel(st_before)
    check_funnel(st_after)
    for engine in engines + [store.base.engine(store)]:
        check_no_fallback(engine)
    report("e", store.plan, full.num_sets, st_before, wall, comp,
           f"appends=3x{APPEND_ROWS} batch_rows={batch.num_sets} "
           f"pairs={len(before)} compact_s={compact_s:.3f} "
           f"pairs_equal_across_compaction=True "
           f"reference_rows_checked={n_checked}")


def four_chips(args) -> int:
    from jax.sharding import Mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found "
          f"{len(devices)}")
    mesh = Mesh(np.array(devices[:4]), ("data",))
    corpus = make_corpus(FOUR_CHIP_SETS, args.seed)
    log(f"[4] corpus N={corpus.num_sets} seed={args.seed}")
    fields = ("total_pairs", "candidates", "verified_true",
              "candidates_generated", "postings_expanded", "overflow_blocks")

    # sharded-indexed against the single-device indexed driver.
    sharded = JoinEngine(corpus, SIM, TAU_INDEXED, mesh=mesh, axis="data",
                         planner=JoinPlanner(b=128))
    check(sharded.plan.driver == "sharded-indexed",
          f"4-chip planner picked {sharded.plan.driver}")
    (sp, sst), wall, comp = timed(
        lambda: sharded.self_join(return_stats=True))
    slabs = sharded.prepared.sharded_postings(SIM, TAU_INDEXED, 1, 4)
    placed = {d for a in slabs._device[1] for s in a.addressable_shards
              for d in [s.device]}
    check(len(placed) == 4, f"postings slabs sit on {len(placed)} devices")
    single = JoinEngine(corpus, SIM, TAU_INDEXED, plan=dataclasses.replace(
        sharded.plan, driver="indexed", reasons=("single-device twin",)))
    (ip, ist), iwall, _ = timed(lambda: single.self_join(return_stats=True))
    check(np.array_equal(sp, ip), "sharded-indexed pairs differ from indexed")
    check(all(getattr(sst, f) == getattr(ist, f) for f in fields),
          f"sharded-indexed stats {sst} differ from indexed {ist}")
    check_no_fallback(sharded)
    check_no_fallback(single)
    report("4 sharded-indexed", sharded.plan, corpus.num_sets, sst, wall,
           comp, f"pairs={len(sp)} slab_devices={len(placed)} "
           f"equal_to_indexed=True indexed_wall={iwall:.3f}")

    # ring against the single-device blocked driver.
    ring = JoinEngine(corpus, SIM, TAU_BLOCKED, mesh=mesh, axis="data",
                      planner=JoinPlanner(b=B_BLOCKED))
    check(ring.plan.driver == "ring", f"4-chip planner picked "
          f"{ring.plan.driver}")
    (rp, rst), wall, comp = timed(lambda: ring.self_join(return_stats=True))
    blocked = JoinEngine(corpus, SIM, TAU_BLOCKED,
                         plan=JoinPlanner(b=B_BLOCKED).plan(
                             SIM, TAU_BLOCKED, corpus.num_sets, n_devices=1,
                             backend=jax.default_backend()))
    check(blocked.plan.driver == "blocked", "single-device twin not blocked")
    (bp, bst), bwall, _ = timed(lambda: blocked.self_join(return_stats=True))
    check(np.array_equal(rp, bp), "ring pairs differ from blocked")
    check(rst.verified_true == bst.verified_true,
          "ring verified_true differs from blocked")
    check_no_fallback(ring)
    check_no_fallback(blocked)
    report("4 ring", ring.plan, corpus.num_sets, rst, wall, comp,
           f"pairs={len(rp)} equal_to_blocked=True blocked_wall={bwall:.3f} "
           f"(ring counts the unwindowed grid, blocked the length window: "
           f"only pairs and verified_true are comparable)")
    return 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded-indexed and ring on 4 chips")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; there is "
              f"no CPU path", file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    dev = jax.devices()[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__} cache={cache}")

    if args.four_chips:
        count = four_chips(args)
    else:
        rng = np.random.default_rng(args.seed)
        (corpus, small), gen_s, _ = timed(lambda: (
            make_corpus(N_SETS, args.seed),
            make_corpus(SMALL_SETS, args.seed + 100)))
        ref, ref_s, _ = timed(lambda: Reference(corpus))
        log(f"corpus N={corpus.num_sets} max_len={corpus.max_len} "
            f"small N={small.num_sets} seed={args.seed} "
            f"(set-up: generate {gen_s:.1f}s, reference index {ref_s:.1f}s)")
        engine = phase_indexed(corpus, ref, small, rng, b=128)
        phase_indexed(corpus, ref, small, rng, b=512)
        phase_blocked(corpus, ref, small, rng)
        phase_probe(engine, corpus, ref, args, rng)
        phase_session(small, args)
        phase_store(small, args, rng)
        count = len(jax.devices())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
