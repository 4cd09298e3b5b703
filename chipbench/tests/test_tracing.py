"""The reduction from a trace to busy time, idle share, kernel time per
layer and the breakdown, on synthetic intervals and on a small trace
recorded on a TPU v5 lite (``data/small.xplane.pb``)."""

import os

import numpy as np
import pytest

from chipbench import harness, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace():
    dev = tracing.DeviceOps(
        names=["fusion.1", "tile_kernel", "tile_kernel", "copy.2"],
        start=np.array([0.0, 50.0, 100.0, 300.0]),
        end=np.array([100.0, 80.0, 200.0, 400.0]))
    host = [("bench.join", 0.0, 1000.0), ("bench.prepare", 190.0, 320.0),
            ("PjitFunction(step)", 390.0, 900.0)]
    return tracing.Trace(window=(0.0, 1000.0), devices=[dev],
                         host_spans=host)


def test_union_merges_overlaps():
    iv = tracing.union(np.array([5.0, 0.0, 9.0, 20.0]),
                       np.array([8.0, 6.0, 12.0, 21.0]))
    assert iv.tolist() == [[0.0, 8.0], [9.0, 12.0], [20.0, 21.0]]


def test_busy_idle_and_layers():
    tr = _trace()
    assert tracing.busy_seconds(tr) == pytest.approx(300e-9)
    assert tracing.window_seconds(tr) == pytest.approx(1000e-9)
    # fusion.1 holds the first tile_kernel: only leaves count.
    assert tracing.layer_seconds(tr, ["tile_"]) == pytest.approx(130e-9)
    assert tracing.top_ops(tr)[0] == ["tile_kernel", pytest.approx(130e-9)]
    assert tracing.layer_seconds(tr, ["fusion"]) == 0.0
    gaps = dict(tracing.idle_gaps(tr))
    # [200, 300) falls in bench.prepare, [400, 1000) in the dispatch span.
    assert gaps == {"bench.prepare": pytest.approx(100e-9),
                    "PjitFunction(step)": pytest.approx(600e-9)}


def test_events_outside_the_window_do_not_count():
    tr = _trace()
    tr.window = (60.0, 350.0)
    assert tracing.busy_seconds(tr) == pytest.approx((200 - 60 + 50) * 1e-9)
    assert tracing.layer_seconds(tr, ["tile_"]) == pytest.approx(120e-9)


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """A window recorded on one TPU v5 lite: a blocked self-join (Jaccard
    0.5, b 512) then an indexed one (0.9, b 128) of 2,048 DBLP-like sets,
    inside the `bench.window` span."""
    import gzip

    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(os.path.join(DATA, "small.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return str(path)


def test_chip_trace_reduces(chip_trace):
    tr = tracing.load(chip_trace)
    assert len(tr.devices) == 1
    busy, window = tracing.busy_seconds(tr), tracing.window_seconds(tr)
    assert 0 < busy < window < 1.0
    ops = tracing.top_ops(tr)
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0
    assert all(name.startswith("jit_") for name, _ in ops)
    gaps = tracing.idle_gaps(tr)
    assert gaps and sum(t for _, t in gaps) == pytest.approx(window - busy)


def test_the_verdict_name_table_finds_each_kernel(chip_trace):
    tr = tracing.load(chip_trace)
    patterns = tracing.load_layer(harness.BENCH_DIR, "bitmap_verdict")
    total = tracing.layer_seconds(tr, patterns)
    per = {p: tracing.layer_seconds(tr, [p]) for p in patterns}
    # The blocked step's tile kernel and count prepass, and the indexed
    # step's pair verdict, all ran; nothing else matches.
    assert per["^%candidate_matrix(\\.\\d+)? = "] > 0
    assert per["^%count_candidates(\\.\\d+)? = "] > 0
    assert per["^%pair_verdict(\\.\\d+)? = "] > 0
    assert total == pytest.approx(sum(per.values()))
    assert 0 < total < tracing.busy_seconds(tr)
    assert tracing.layer_seconds(tr, ["^%entry_filter"]) > 0
