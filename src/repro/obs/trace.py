"""Program spans on the profiler's clock (DESIGN.md §12).

There is one span system: the JAX profiler's.  ``trace_span(name,
**meta)`` is a ``jax.profiler.TraceAnnotation``: it is always in the
code, and the profiler session decides whether anything is recorded.
Recorded spans land in the same trace as the device's ops, on the same
clock, so an idle gap of the chip can be put down to the host work that
overlapped it.  With no session a span costs about a microsecond.

Spans belong on the host, around work that the host does.  Inside jitted
code a span would fire once, at trace time; there, name the stage with
``jax.named_scope`` instead, which rides into each op's ``op_name``
metadata and costs nothing at run time.

Meta values are encoded into the span's name by the profiler, so they
must not contain ``,``, ``#`` or ``=``.

Usage::

    with profile("/tmp/run"):                 # one Perfetto timeline
        with trace_span("serve.step", step=3) as sp:
            ...
            sp.set_metadata(slots=2)          # meta known only later
"""
from __future__ import annotations

import contextlib
import gc

import jax

__all__ = ["gc_spans", "profile", "trace_span"]


def trace_span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (dotted: ``serve.admit``), recorded only
    while a profiler session is on."""
    return jax.profiler.TraceAnnotation(name, **meta)


@contextlib.contextmanager
def gc_spans():
    """Record each pass of Python's garbage collector as a ``py.gc`` span
    (meta: the generation collected) while the block runs."""
    open_spans = []

    def callback(phase, info):
        if phase == "start":
            sp = trace_span("py.gc", generation=info["generation"])
            sp.__enter__()
            open_spans.append(sp)
        elif open_spans:
            open_spans.pop().__exit__(None, None, None)

    gc.callbacks.append(callback)
    try:
        yield
    finally:
        gc.callbacks.remove(callback)


@contextlib.contextmanager
def profile(logdir: str | None):
    """Profile the block into ``logdir`` (an ``.xplane.pb`` and a
    ``perfetto_trace.json.gz`` for https://ui.perfetto.dev), with the
    collector's passes as ``py.gc`` spans; does nothing if ``logdir`` is
    empty.  The profiler's per-call Python tracer stays off: the program's
    own spans say what the host was doing."""
    if not logdir:
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(logdir, create_perfetto_trace=True, profiler_options=opts), gc_spans():
        yield
