"""The harness: cells, configurations and metrics are found by name, so a
later PR adds them as files; a run off the chip is refused; and a run whose
timed path is broken comes out not correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness, roofline
from chipbench.tests.conftest import tiny

ROOT = os.path.dirname(harness.BENCH_DIR)
SEED = 2 ** 31 + 29


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _tiny_cell(name, n_sets=1000, clusters=10, **traffic):
    cell = harness.resolve(_spec(), name, ROOT)
    config = os.path.basename(cell.config["name"])
    cell.config = tiny(config, n_sets=n_sets, clusters=clusters)
    cell.traffic.update(traffic)
    return cell


def _run(cell, tmp_path, seconds=0.5, trace=False):
    return harness.run_cell(cell, SEED, seconds, trace,
                            out_dir=str(tmp_path), require_tpu=False,
                            log=lambda msg: None)


def test_a_new_cell_and_metric_are_files_only(tmp_path):
    """Copy the benchmark's directory, add a configuration, a traffic mix
    and a per-layer metric as new files, name them in a copy of the
    benchmark file, and run the new cell: nothing existing is edited."""
    bench = tmp_path / "chipbench"
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = tiny("dblp-dedup", n_sets=800, clusters=8)
    cfg["name"] = "mini-dedup"
    (bench / "configs" / "mini-dedup.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "j080.json").write_text('{"tau": 0.8, "b": 64}')
    (bench / "metrics" / "pairs_per_join.join.py").write_text(
        "def read(run):\n"
        "    stats = run.join_stats\n"
        "    return sum(s.verified_true for s in stats) / len(stats)\n")
    spec = _spec()
    spec["configs"].append({"name": "mini-dedup", "source": "test",
                            "file": "chipbench/configs/mini-dedup.json",
                            "reduced": ["n_sets"], "why": "test"})
    spec["workloads"].append({"name": "mini-dedup.j080",
                              "config": "mini-dedup", "traffic": "j080",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "join_s":
            m["workloads"].append("mini-dedup.j080")
    spec["per_layer"].append({"name": "pairs_per_join.join", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "exact verification",
                              "moves": "join_s"})
    cell = harness.resolve(spec, "mini-dedup.j080", str(tmp_path),
                           bench_dir=str(bench))
    assert cell.traffic == {"tau": 0.8, "b": 64}
    assert [m["name"] for m in cell.end_to_end] == ["join_s", "setup_s"]
    line = _run(cell, tmp_path / "out")
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"join_s", "setup_s"}
    assert line["metrics"]["join_s"]["value"] > 0
    assert list(line)[-1] == "checks"
    traced = _run(cell, tmp_path / "out", trace=True)
    assert traced["correct"]
    # Metrics that list their cells do not list this one.
    assert set(traced["metrics"]) == {"pairs_per_join.join"}
    assert traced["metrics"]["pairs_per_join.join"]["value"] > 0


def test_each_cell_resolves_to_its_files():
    spec = _spec()
    for w in spec["workloads"]:
        cell = harness.resolve(spec, w["name"], ROOT)
        assert cell.entry().run
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
    assert roofline.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12


def test_off_the_chip_the_harness_refuses():
    with pytest.raises(SystemExit):
        harness.device_info(1, require_tpu=True)


def test_the_command_fails_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "dblp-dedup.j090",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_the_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "dblp-dedup.j090",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


# -- the timed path broken underneath: `correct` must come out false --------

def _drop_half(pairs):
    return pairs[::2]


def _alter_one(pairs):
    pairs = pairs.copy()
    pairs[0, 1] = (pairs[0, 1] + 1) if pairs[0, 1] + 1 != pairs[0, 0] \
        else pairs[0, 1] + 2
    return pairs


@pytest.mark.parametrize("fault", [_drop_half, _alter_one])
def test_a_broken_join_is_not_correct(monkeypatch, tmp_path, fault):
    from repro.core.engine import JoinEngine

    original = JoinEngine.self_join

    def broken(self, *, return_stats=False):
        pairs, stats = original(self, return_stats=True)
        return (fault(pairs), stats) if return_stats else fault(pairs)

    monkeypatch.setattr(JoinEngine, "self_join", broken)
    line = _run(_tiny_cell("dblp-dedup.j090"), tmp_path)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    assert line["checks"]["missing_pairs"]["value"] > 0


def test_a_compile_inside_the_window_is_not_correct(monkeypatch, tmp_path):
    """A program that compiles inside the measured window (here a new
    jitted function per join) fails the run's ``compiles_in_window``
    check."""
    import jax
    import jax.numpy as jnp
    from repro.core.engine import JoinEngine

    original = JoinEngine.self_join

    def compiling(self, *, return_stats=False):
        jax.jit(lambda x: x + 1)(jnp.zeros(3)).block_until_ready()
        return original(self, return_stats=return_stats)

    monkeypatch.setattr(JoinEngine, "self_join", compiling)
    line = _run(_tiny_cell("dblp-dedup.j090"), tmp_path)
    assert line["correct"] is False
    assert line["checks"]["compiles_in_window"]["value"] > 0
    assert line["checks"]["missing_pairs"]["value"] == 0
