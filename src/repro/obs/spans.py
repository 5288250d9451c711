"""Host spans on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``. While a
profiler trace runs (``jax.profiler.trace`` / ``start_trace``), the span
lands on the trace's host plane, on the same clock as the device planes,
with ``args`` as its arguments, so a reduction of the trace can name what
the host was doing while the chip sat idle. With no trace running it
records nothing and costs only the annotation's construction. JAX is
imported on the first call: importing ``repro.obs`` does not import it.
"""
from __future__ import annotations

_annotation = None      # jax.profiler.TraceAnnotation, once imported


def span(name: str, **args):
    """A context manager: a host span named ``name`` in the profiler's trace."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(name, **args)
