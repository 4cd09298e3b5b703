"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

Every kernel that ``impl="auto"`` resolves to on a TPU is compiled here for
a described (not attached) ``v5e:2x2`` topology at real widths — 4096-row
tiles, 16384 candidates — at b=128 and b=512, plus the indexed driver's
whole fused chunk step.  A compile is not a chip run: it proves only that
Mosaic and XLA accept the program.  ``test_auto_routes_only_to_compiled``
pins ``auto`` to exactly the impls compiled here.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library at a time, and every pytest
worker imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops

N_ROWS = 4096
N_CAND = 16384
WIDTHS = (128, 512)
ALL_WIDTHS = (64, 128, 256, 512, 1024)

#: (op, impl) pairs compiled below — what ``auto`` may resolve to on a TPU.
COMPILED = {
    ("hamming_matrix", "swar"),
    ("candidate_matrix", "swar"),
    ("count_candidates", "swar"),
    ("pair_verdict", "swar_tiled"),
    ("entry_filter", "swar"),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(one_chip, monkeypatch):
    """Compile as the chip would: ``auto`` resolves for a TPU, kernels are
    not interpreted, nothing is cached across processes, and no trace made
    for the CPU is reused."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_text(fn, *args):
        return jax.jit(fn).lower(*args).compile().as_text()

    yield spec, compile_text
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _dense_op(op, spec, b):
    words = spec((N_ROWS, b // 32), jnp.uint32)
    lens = spec((N_ROWS,), jnp.int32)
    kw = dict(impl="auto")
    if op == "hamming_matrix":
        return functools.partial(kops.hamming_matrix, **kw), (words, words)
    if op == "candidate_matrix":
        return (functools.partial(kops.candidate_matrix, sim="jaccard",
                                  tau=0.5, self_join=True, **kw),
                (words, words, lens, lens))
    return (functools.partial(kops.count_candidates, sim="jaccard", tau=0.5,
                              self_join=True, **kw),
            (words, words, lens, lens, lens, lens))


@pytest.mark.parametrize("b", WIDTHS)
@pytest.mark.parametrize("op", ["hamming_matrix", "candidate_matrix",
                                "count_candidates"])
def test_dense_kernel_compiles(tpu_compile, op, b):
    spec, compile_text = tpu_compile
    assert kops.resolve_impl("auto", b) == "swar"
    fn, args = _dense_op(op, spec, b)
    assert "tpu_custom_call" in compile_text(fn, *args)


@pytest.mark.parametrize("b", WIDTHS)
def test_pair_verdict_compiles(tpu_compile, b):
    spec, compile_text = tpu_compile
    assert kops._resolve_pairwise_impl("auto", b) == "swar_tiled"
    words = spec((N_CAND, b // 32), jnp.uint32)
    lens = spec((N_CAND,), jnp.int32)
    fn = functools.partial(kops.pair_verdict, sim="jaccard", tau=0.9,
                           impl="auto")
    assert "tpu_custom_call" in compile_text(fn, words, words, lens, lens)


def test_entry_filter_compiles(tpu_compile):
    spec, compile_text = tpu_compile
    assert kops._resolve_entry_impl("auto") == "swar"
    ints = [spec((N_CAND,), jnp.int32)] * 8
    valid = spec((N_CAND,), jnp.bool_)
    fn = functools.partial(kops.entry_filter, sim="jaccard", tau=0.9,
                           self_join=True, impl="auto")
    assert "tpu_custom_call" in compile_text(fn, *ints, valid)


@pytest.mark.parametrize("b", WIDTHS)
def test_indexed_chunk_step_compiles(tpu_compile, b):
    """The indexed driver's whole fused step (expand, entry filter, dedup,
    pairwise verdict, verification) at a 4096-row probe chunk and 16384
    candidate slots, shaped from a real prepared corpus."""
    from repro.core.engine import prepare
    from repro.data.collections import uniform_collection
    from repro.index import candidates as cands

    spec, _ = tpu_compile
    prep = prepare(uniform_collection(N_ROWS, avg_size=40, n_tokens=20000,
                                      seed=0))
    args, statics = cands.chunk_step_spec(prep, sim="jaccard", tau=0.9, b=b,
                                          probe_block=N_ROWS)
    statics["cap"] = N_CAND
    shapes = [spec(np.shape(a), a.dtype) for a in args]
    text = cands._indexed_chunk_step.lower(*shapes, **statics).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", ALL_WIDTHS)
def test_auto_routes_only_to_compiled(monkeypatch, b):
    """On a TPU, every impl ``auto`` resolves to is one this file compiles."""
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    resolved = {
        ("hamming_matrix", kops.resolve_impl("auto", b)),
        ("candidate_matrix", kops.resolve_impl("auto", b)),
        ("count_candidates", kops.resolve_impl("auto", b)),
        ("pair_verdict", kops._resolve_pairwise_impl("auto", b)),
        ("entry_filter", kops._resolve_entry_impl("auto")),
    }
    assert resolved <= COMPILED
