"""Names the join gives its work in a profiler trace.

Device stages are ``jax.named_scope`` names inside the jitted steps; they
extend each operation's HLO ``op_name`` (the ``tf_op`` of a device event in
a ``jax.profiler`` trace) and change no compiled code.  One flat
vocabulary, no nesting: where an operation's name holds two stages, the
innermost counts.

* ``expand`` — postings CSR expansion and the entry filters;
* ``dedup`` — sort and unique of the candidate entries into ``cap`` slots;
* ``window`` — the integer length-window mask;
* ``verdict`` — bitmap word gathers and the bitmap-filter verdict;
* ``compact`` — ``nonzero`` into a fixed-capacity buffer, the pair stack;
* ``verify`` — exact overlap verification and the accept.

Host spans are :func:`span` (a ``jax.profiler.TraceAnnotation``), named
without per-call arguments: ``join`` around one join, ``join.count``,
``join.dispatch``, ``join.sync``, ``join.fetch``, ``join.fallback`` per
device step of the driver loop, ``join.finish`` around the final merge, and
``prepare.sort``, ``prepare.upload``, ``prepare.bitmap``, ``prepare.window``,
``prepare.postings`` around the builds of a prepared collection.  An idle
annotation costs about a microsecond; it records only under
``jax.profiler.trace``.
"""

from __future__ import annotations

import jax

STAGES = EXPAND, DEDUP, WINDOW, VERDICT, COMPACT, VERIFY = (
    "expand", "dedup", "window", "verdict", "compact", "verify")


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation(name)
