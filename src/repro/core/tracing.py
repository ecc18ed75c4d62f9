"""Host spans of the engine, on the profiler's clock.

``span(name, **args)`` marks one stretch of host work as
``dfo.<name>`` in a ``jax.profiler`` trace, on the host line of the
thread that runs it and on the same clock as the device's ops.  Names
are constant strings; ``args`` are the integer counts at that boundary
(items, chunks, edges, bytes).  A count known only at the end of the
span is added with ``set_metadata`` on the returned object::

    with span("chunk.read", q=q, k=k) as sp:
        ...
        sp.set_metadata(bytes=nbytes)

With no profiler session running a span costs about a microsecond and
records nothing.  A span never waits for the device: device work it
dispatches may finish after the span ends.  Spans sit at the granularity
of a batch or a phase, never per edge or per vertex.

Inside jitted code the phases are marked with ``jax.named_scope`` instead
(``generate``, ``filter``, ``dispatch``, ``combine``, ``apply``): the
scope lands in each op's name stack, which the device trace carries.
"""
from __future__ import annotations

import jax

PREFIX = "dfo."


def span(name: str, **args: int) -> jax.profiler.TraceAnnotation:
    """A context manager that marks ``dfo.<name>`` on this thread."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
