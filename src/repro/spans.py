"""Named host spans of the allocator, recorded by JAX's profiler.

Each span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``: a
profiler session (``jax.profiler.trace``, ``start_trace`` or a capture
through ``start_server``) writes it into the same ``.xplane.pb`` as the
device's operations, on the device trace's clock.  With no session
active a span costs about a microsecond, so the spans stay in the code
unconditionally.  The ``repro.`` prefix tells them apart from the runtime's
own events in a trace.

Spans wrap synchronous code only: annotations of one thread must nest, so
none may be open across an ``await``.  Metadata values are ints or short
strings, the layer's counts at the span's boundary.  ``docs/OPERATIONS.md``
says what each span tells an operator; ``PERF.md`` which benchmark metric
reads it.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "repro."

#: Every span of the program, ``(name, layer)``: the layer is the module
#: group of ``PERF.md``'s layer map that the span's code belongs to.
SPANS = (
    ("wire.offer", "wire"),
    ("wire.push", "wire"),
    ("allocd.enqueue", "daemon"),
    ("allocd.fold", "daemon"),
    ("allocd.flush", "daemon"),
    ("allocd.device_wait", "daemon"),
    ("session.apply", "session"),
    ("engine.solve", "session"),
    ("plan.chunk", "planner"),
    ("plan.stack", "planner"),
    ("plan.pull", "planner"),
)


def span(name: str, **meta) -> TraceAnnotation:
    """The annotation ``repro.<name>``, carrying ``meta`` into the trace.

    Parameters
    ----------
    name : str
        A name of :data:`SPANS`.
    **meta : int or str
        The layer's counts at this boundary (a ticket's ``seq``, a chunk's
        ``lanes``).

    Returns
    -------
    jax.profiler.TraceAnnotation
        A context manager; use it in a ``with`` statement.
    """
    return TraceAnnotation(PREFIX + name, **meta)
