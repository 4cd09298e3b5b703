"""The join names its work for the profiler: device stages (``jax.
named_scope``) in the compiled HLO of every step, host spans around the
driver loop and the prepared-collection builds, and the ``slots`` counter,
which stays out of the funnel's equality."""

import collections
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.core import join, verify
from repro.core.engine import JoinEngine, prepare
from repro.core.plan import JoinPlan
from repro.data.collections import skewed_collection
from repro.index import candidates
from repro.kernels import ops as kops

SIM, TAU = "jaccard", 0.6


@pytest.fixture(scope="module")
def prep():
    return prepare(skewed_collection(300, seed=5))


def _stages(hlo: str) -> set:
    """The stages named in a compiled module's ``op_name`` metadata."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found |= set(op_name.split("/")) & set(tracing.STAGES)
    return found


def test_indexed_chunk_step_names_its_stages(prep):
    args, statics = candidates.chunk_step_spec(prep, sim=SIM, tau=TAU, b=64,
                                               probe_block=128)
    hlo = candidates._indexed_chunk_step.lower(
        *args, **statics).compile().as_text()
    assert _stages(hlo) == {"expand", "dedup", "verdict", "verify",
                            "compact"}


def test_resident_block_step_names_its_stages(prep):
    tokens, lengths = prep.device_arrays()
    words = prep.bitmap_words(64, "set")
    _, _, lo, hi = prep.length_window_int(SIM, TAU)
    need = verify.min_overlap_table_dev(SIM, TAU, prep.max_len, prep.max_len)
    hlo = join._resident_block_step.lower(
        tokens, lengths, words, tokens, lengths, words, lo, hi, need,
        jnp.int32(0), jnp.int32(0), sim=SIM, tau=TAU, cap=256, diag=True,
        cutoff=1 << 30, impl="auto").compile().as_text()
    assert _stages(hlo) == {"window", "verdict", "compact", "verify"}


def test_count_prepass_and_fallback_name_their_stages(prep):
    _, lengths = prep.device_arrays()
    words = prep.bitmap_words(64, "set")
    _, _, lo, hi = prep.length_window_int(SIM, TAU)
    count = kops.count_candidates.lower(
        words, words, lengths, lengths, lo, hi, sim=SIM, tau=TAU,
        impl="auto").compile().as_text()
    assert _stages(count) == {"verdict"}
    fallback = candidates._fallback_mask.lower(
        words, lengths, words, lengths, lo, hi, jnp.int32(0), sim=SIM,
        tau=TAU, cutoff=1 << 30, impl="auto",
        self_join=True).compile().as_text()
    assert _stages(fallback) == {"verdict", "window"}


def _host_spans(trace_dir: str) -> collections.Counter:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "join" or ev.name.startswith(("join.",
                                                            "prepare.")):
                    names[ev.name] += 1
    return names


@pytest.mark.parametrize("driver", ["indexed", "blocked"])
def test_a_traced_join_leaves_its_spans(driver, tmp_path):
    col = skewed_collection(600, seed=3)
    plan = JoinPlan(sim=SIM, tau=TAU, driver=driver, b=64, block=256,
                    compaction="device")
    JoinEngine(col, SIM, TAU, plan=plan).self_join()        # compile
    with jax.profiler.trace(str(tmp_path)):
        engine = JoinEngine(col, SIM, TAU, plan=plan)
        pairs, stats = engine.self_join(return_stats=True)
    spans = _host_spans(str(tmp_path))
    steps = stats.blocks_total - stats.blocks_skipped
    assert len(pairs) > 0 and steps > 1
    assert spans["join"] == 1 and spans["join.finish"] == 1
    assert spans["join.dispatch"] == spans["join.sync"] > 1
    assert 0 < spans["join.fetch"] <= spans["join.dispatch"]
    assert spans["join.count"] >= spans["join.dispatch"]
    builds = {"prepare.sort", "prepare.upload", "prepare.bitmap",
              "prepare.window"}
    if driver == "indexed":
        builds.add("prepare.postings")
    assert {n for n in spans if n.startswith("prepare.")} == builds
    assert all(spans[n] == 1 for n in builds)


def test_a_cached_build_opens_no_span(prep, tmp_path):
    prep.bitmap_words(64, "set")
    with jax.profiler.trace(str(tmp_path)):
        prep.bitmap_words(64, "set")
        prep.device_arrays()
    assert not _host_spans(str(tmp_path))


@pytest.mark.parametrize("driver", ["indexed", "blocked"])
def test_slots_sum_the_dispatched_capacities(driver):
    col = skewed_collection(500, seed=7)
    kw = dict(b=64, return_stats=True)
    if driver == "indexed":
        _, stats = candidates.indexed_bitmap_join(col, SIM, TAU,
                                                  probe_block=128, **kw)
        # Every chunk's entry stream is sized from its exact expansion.
        assert stats.postings_expanded <= stats.slots
    else:
        _, stats = join.blocked_bitmap_join(col, SIM, TAU, block=128,
                                            compaction="device", **kw)
        assert stats.candidates <= stats.slots
    assert stats.slots > 0 and stats.slots % 128 == 0


def test_slots_stay_out_of_the_funnel():
    a = join.JoinStats(candidates=3, slots=128)
    b = join.JoinStats(candidates=3, slots=4096)
    assert a == b
    assert "slots" not in a.to_dict()
    assert dataclasses.replace(a, slots=0) == a
    _, host = join.blocked_bitmap_join(
        skewed_collection(300, seed=2), SIM, TAU, b=64, block=128,
        return_stats=True)
    assert host.slots == 0                     # host compaction: no step


def test_span_is_a_profiler_annotation():
    with tracing.span("join.test") as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
    assert len(set(tracing.STAGES)) == 6
