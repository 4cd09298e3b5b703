"""The program's names in a trace: ``xplane.py`` reads each device
operation's ``tf_op``, ``stages.py`` reduces a trace to device seconds per
join stage and idle seconds per program span, and the five readers built on
them (and on ``JoinStats.slots``) read hand-built records, or nothing."""

import gzip
import os
import shutil

import numpy as np
import pytest

from chipbench import harness, stages, tracing, xplane
from chipbench.harness import Record

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("candidates_s.join", "compact_s.join", "verify_s.join",
           "loop_idle.join", "slot_fill.join")
STEPS = ("jit__indexed_chunk_step", "jit__resident_block_step",
         "jit_count_candidates")


def _unzip(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name[:-len(".gz")]
    with gzip.open(os.path.join(DATA, name)) as f:
        path.write_bytes(f.read())
    return str(path)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """The PR-12 chip trace, recorded before the program named its
    stages (see ``test_tracing.py``)."""
    return _unzip(tmp_path_factory, "small.xplane.pb.gz")


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """A chip trace of the named program: ``scripts/record_scoped_trace.py``
    (a blocked and an indexed self-join of 4,096 DBLP-shaped sets)."""
    return _unzip(tmp_path_factory, "scoped.xplane.pb.gz")


def _reader(name):
    return harness.load_module(os.path.join(harness.BENCH_DIR, "metrics",
                                            name + ".py"))


# -- xplane.py -------------------------------------------------------------

def test_xplane_reads_each_operations_tf_op(small):
    tf_ops = xplane.device_tf_ops(small)
    fusion = [n for n in tf_ops if n.startswith("%fusion.33 = ")]
    assert len(fusion) == 1
    assert tf_ops[fusion[0]] == "jit(_resident_block_step)/reduce_sum:"
    # Every name is an operation the profiler reports on a device.
    names = {n for d in tracing.load(small).devices for n in d.names}
    assert set(tf_ops) <= names and len(tf_ops) > 100


def test_xplane_refuses_what_is_not_a_trace(tmp_path):
    path = tmp_path / "bad.pb"
    path.write_bytes(b"\x0f\x00")       # field 1, wire type 7
    with pytest.raises(ValueError):
        xplane.device_tf_ops(str(path))


def test_summarize_reads_as_it_did(small):
    """The harness's own reduction of the PR-12 trace, value for value:
    the stage reduction lives beside it and changes none of it."""
    patterns = tracing.load_layer(harness.BENCH_DIR, "bitmap_verdict")
    got = tracing.summarize(small, {"bitmap_verdict": patterns})
    assert set(got) == {"busy_s", "window_s", "layers", "device_ops",
                        "idle_gaps"}
    assert got["busy_s"] == pytest.approx(0.206763982, rel=1e-12)
    assert got["window_s"] == pytest.approx(0.249313834, rel=1e-12)
    assert got["layers"] == {
        "bitmap_verdict": pytest.approx(0.001348567, rel=1e-9)}
    want_ops = [
        ("jit__indexed_chunk_step/%fusion.76", 0.059816028),
        ("jit__resident_block_step/%fusion.7", 0.036602385),
        ("jit__indexed_chunk_step/%fusion.18", 0.010791613),
        ("jit__indexed_chunk_step/%fusion.19", 0.009077824),
        ("jit__indexed_chunk_step/%fusion.29", 0.004583121),
        ("jit__indexed_chunk_step/%fusion.28", 0.004576022),
        ("jit__indexed_chunk_step/%fusion.26", 0.004516278),
        ("jit__indexed_chunk_step/%fusion.8", 0.004512333),
        ("jit__indexed_chunk_step/%fusion.11", 0.00449798),
        ("jit__indexed_chunk_step/%fusion.17", 0.004496261)]
    assert [n for n, _ in got["device_ops"]] == [n for n, _ in want_ops]
    for (_, t), (_, want) in zip(got["device_ops"], want_ops):
        assert t == pytest.approx(want, rel=1e-9)
    want_gaps = [
        ("(no host span)", 0.033689079), ("np.asarray(jax.Array)", 0.005066413),
        ("tpu::System::Execute=>Done", 0.003608493),
        ("bench.blocked", 0.000185848),
        ("CommonPjRtLoadedExecutable::ExecutePrepare", 7e-09),
        ("Transpose::Execute", 7e-09), ("Acquire semaphore", 3e-09),
        ("PjitFunction(convert_element_type)", 2e-09)]
    assert [n for n, _ in got["idle_gaps"]] == [n for n, _ in want_gaps]
    for (_, t), (_, want) in zip(got["idle_gaps"], want_gaps):
        assert t == pytest.approx(want, rel=1e-6)


# -- stages.py ---------------------------------------------------------------

def test_the_vocabulary_is_the_programs():
    from repro import tracing as program

    assert stages.STAGES == program.STAGES


@pytest.mark.parametrize("tf_op, stage", [
    ("jit(_indexed_chunk_step)/expand/jit(searchsorted)/while:", "expand"),
    ("jit(_resident_block_step)/verify/while/body/closed_call/gather:",
     "verify"),
    ("jit(f)/compact/verify/add:", "verify"),          # innermost counts
    ("jit(_resident_block_step)/jit(cumsum)/reduce_window:", "(unscoped)"),
    ("reduce_window_sum:", "(unscoped)"),
    ("jit(f)/windowed/add:", "(unscoped)"),            # whole names only
    ("", "(unscoped)"),
])
def test_stage_of(tf_op, stage):
    assert stages.stage_of(tf_op) == stage


def _trace():
    dev = tracing.DeviceOps(
        names=["while.1", "fusion.1", "fusion.2", "copy.3", "fusion.4"],
        start=np.array([0.0, 10.0, 60.0, 300.0, 500.0]),
        end=np.array([100.0, 50.0, 100.0, 400.0, 1200.0]))
    host = [("bench.self_join", 0.0, 1000.0), ("join", 0.0, 1000.0),
            ("join.dispatch", 100.0, 150.0), ("join.sync", 150.0, 350.0),
            ("prepare.bitmap", 420.0, 480.0),
            ("PjitFunction(step)", 460.0, 470.0)]
    return tracing.Trace(window=(0.0, 1000.0), devices=[dev],
                         host_spans=host)


def test_scopes_count_leaves_in_the_window():
    tf_ops = {"while.1": "jit(s)/verify/while:",
              "fusion.1": "jit(s)/verify/while/body/gather:",
              "fusion.2": "jit(s)/compact/nonzero:",
              "fusion.4": "jit(s)/dedup/sort:"}
    got = stages.scopes(_trace(), tf_ops)
    # while.1 holds fusion.1 and fusion.2: only leaves count; copy.3 has no
    # tf_op; fusion.4 is clipped at the window's end.
    assert got == {"verify": pytest.approx(40e-9),
                   "compact": pytest.approx(40e-9),
                   "(unscoped)": pytest.approx(100e-9),
                   "dedup": pytest.approx(500e-9)}


def test_program_idle_takes_the_innermost_program_span():
    got = stages.program_idle(_trace())
    # Gaps: [100, 300) mid 200 in join.sync; [400, 500) mid 450 in
    # prepare.bitmap (the JAX span inside it is not the program's).
    assert got == {"join.sync": pytest.approx(200e-9),
                   "prepare.bitmap": pytest.approx(100e-9)}
    tr = _trace()
    tr.host_spans = [s for s in tr.host_spans if s[0] == "bench.self_join"]
    assert stages.program_idle(tr) == {"(none)": pytest.approx(300e-9)}


def test_a_trace_without_names_reads_as_absent(small, tmp_path):
    """The parent program's trace: every operation unscoped, every gap
    outside a program span, so ``of`` finds nothing to read."""
    got = stages.reduce(small)
    assert set(got["scopes"]) == {"(unscoped)"}
    assert set(got["program_idle"]) == {"(none)"}
    tr = tracing.load(small)
    assert got["scopes"]["(unscoped)"] <= tracing.busy_seconds(tr) + 1e-12
    _place(small, tmp_path, "old")
    run = Record(trace={"window_s": got["window_s"], "busy_s": 1.0},
                 joins=1)
    assert stages.of(run, root=str(tmp_path)) is None
    assert "scopes" not in run.notes


def _place(trace_path, root, cell):
    dest = root / "chipbench_out" / cell / "trace" / "plugins" / "profile" / "t"
    dest.mkdir(parents=True)
    shutil.copy(trace_path, dest / "x.xplane.pb")
    return dest / "x.xplane.pb"


def test_of_finds_the_trace_the_harness_reduced(scoped, small, tmp_path):
    _place(scoped, tmp_path, "a")
    newer = _place(small, tmp_path, "b")
    os.utime(newer, (1e10, 1e10))           # newest, but another window
    summary = tracing.summarize(scoped, {})
    run = Record(trace=summary, joins=2)
    found = stages.of(run, root=str(tmp_path))
    assert found is not None and found == stages.reduce(scoped)
    assert run.notes["scopes"] == found["scopes"]
    assert run.notes["program_idle"] == found["program_idle"]
    assert stages.of(run, root="/nonexistent") is found      # cached
    assert stages.of(Record(trace={"window_s": -1.0}),
                     root=str(tmp_path)) is None


def test_the_named_stages_hold_the_steps_device_time(scoped):
    """On the chip, the six stages account for at least 90% of the device
    time of the join's step programs."""
    tr = tracing.load(scoped)
    tf_ops = xplane.device_tf_ops(scoped)
    total = named = 0.0
    seen = set()
    for dev in tr.devices:
        d = tracing._clip(dev, tr.window)
        for name, module, dur, leaf in zip(d.names, d.module, d.end - d.start,
                                           tracing._leaves(d)):
            if leaf and module in STEPS:
                total += dur
                stage = stages.stage_of(tf_ops.get(name, ""))
                if stage in stages.STAGES:
                    named += dur
                    seen.add(stage)
    assert total > 0
    assert named >= 0.9 * total, (named, total)
    assert seen == set(stages.STAGES)


def test_the_recorded_trace_holds_the_join_spans(scoped):
    names = {s[0] for s in tracing.load(scoped).host_spans}
    assert {"join", "join.count", "join.dispatch", "join.sync", "join.fetch",
            "join.finish", "prepare.sort", "prepare.upload", "prepare.bitmap",
            "prepare.window", "prepare.postings"} <= names
    got = stages.reduce(scoped)
    idle = sum(got["program_idle"].values())
    assert got["program_idle"].get("(none)", 0.0) <= 0.2 * idle


# -- the readers -------------------------------------------------------------

def _traced(**kw):
    scopes = {"expand": 3.0, "dedup": 1.0, "window": 0.5, "verdict": 0.25,
              "compact": 2.0, "verify": 6.0, "(unscoped)": 0.125}
    idle = {"join": 0.5, "join.sync": 1.0, "join.count": 0.25,
            "prepare.bitmap": 2.0, "(none)": 4.0}
    fields = dict(trace={"window_s": 20.0, "busy_s": 12.0}, joins=2,
                  stages={"window_s": 20.0, "scopes": scopes,
                          "program_idle": idle})
    fields.update(kw)
    return Record(**fields)


class _Stats:
    def __init__(self, slots=None, postings_expanded=0, candidates=0):
        if slots is not None:
            self.slots = slots
        self.postings_expanded = postings_expanded
        self.candidates = candidates


def test_readers_on_a_traced_record():
    run = _traced()
    read = {name: _reader(name).read for name in READERS}
    assert read["candidates_s.join"](run) == pytest.approx(4.5 / 2)
    assert read["compact_s.join"](run) == pytest.approx(2.0 / 2)
    assert read["verify_s.join"](run) == pytest.approx(6.0 / 2)
    assert read["loop_idle.join"](run) == pytest.approx(100 * 1.75 / 20)
    assert read["slot_fill.join"](run) is None      # no join_stats


def test_slot_fill_reads_the_driver_it_ran():
    read = _reader("slot_fill.join").read
    indexed = [_Stats(1024, postings_expanded=300, candidates=7),
               _Stats(1024, postings_expanded=212, candidates=5)]
    assert read(Record(join_stats=indexed)) == pytest.approx(25.0)
    blocked = [_Stats(512, candidates=128)]
    assert read(Record(join_stats=blocked)) == pytest.approx(25.0)
    # A program without the counter, or a join that dispatched no step.
    assert read(Record(join_stats=[_Stats(candidates=3)])) is None
    assert read(Record(join_stats=[_Stats(0, candidates=3)])) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_their_input(name):
    read = _reader(name).read
    assert read(Record()) is None            # not traced, no JoinStats
    # Traced, but the trace names no stage and no program span (the parent
    # program's): ``stages.of`` has left None on the run.
    assert read(_traced(stages=None)) is None


def test_loop_idle_needs_device_time():
    assert _reader("loop_idle.join").read(
        _traced(trace={"window_s": 20.0, "busy_s": 0.0})) is None
