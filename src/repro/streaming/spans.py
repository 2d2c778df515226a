"""Named host spans on the profiler's clock (DESIGN.md §12.3).

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` while a
profiler session collects (``jax.profiler.start_trace`` or a
``start_server`` capture) and a shared no-op otherwise, at about 0.3 µs
a span: there is no switch and no second sink.  Both support
``set_metadata(**ids)`` before exit, for ids known only at the end.
``trace_gc`` adds Python's garbage collections to the same timeline as
``py.gc`` spans.
"""
from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation


class _Off:
    """The span while no session collects: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **ids) -> None:
        pass


_OFF = _Off()


def span(name: str, **ids):
    """A host span ``name`` with integer ``ids``, recorded only while a
    profiler session collects."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **ids)
    return _OFF


_gc_open = None


def _gc_span(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        if TraceAnnotation.is_enabled():
            _gc_open = TraceAnnotation("py.gc", generation=info["generation"])
            _gc_open.__enter__()
    elif _gc_open is not None:
        open_, _gc_open = _gc_open, None
        open_.__exit__(None, None, None)


def trace_gc() -> None:
    """Put every garbage collection under a ``py.gc`` span (once per
    process; collections do not nest)."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
