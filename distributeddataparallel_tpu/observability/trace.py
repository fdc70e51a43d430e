"""Tracer: nested, low-overhead host-side spans.

``with tracer.span("step", step=gstep):`` stamps ``time.perf_counter``
at entry/exit and emits a ``span`` event with the duration, its nesting
``depth``, and its ``parent`` span name.  That is the ENTIRE cost: two
host clock reads and a dict append.  A span never reads a device value,
so wrapping the dispatch of an async jax computation measures dispatch
time — which is the honest number for an async step.  Wall-clock truth
for device work still comes from the window boundaries where
BoundedDispatch drains; spans covering those drains (log/eval/epoch
edges) include the settled time naturally.

Every span is also a ``jax.profiler.TraceAnnotation`` named
``ddp:<name>`` when ``jax`` is already imported: with no profiler session
that is a flag test, with one the span lands on the host plane of the
same ``.xplane.pb`` as the device operations — one clock, no conversion.

The nesting stack is per thread: a loader's producer thread and the
train loop each nest their own spans.

Module-import rule: stdlib only (see schema.py).
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time

#: prefix of the program's spans in the profiler's trace
ANNOTATION_PREFIX = "ddp:"
_NO_ANNOTATION = contextlib.nullcontext()


def _annotation(name: str, attrs: dict):
    """The profiler's annotation for a span; a no-op context where jax is
    not loaded (this never imports it)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return _NO_ANNOTATION
    return profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **attrs)


class Tracer:
    """Emits nested span records into an EventLog and (optionally) a
    MetricsRegistry histogram per span name.

    ``events`` and ``registry`` are both optional: with neither, a span
    is the profiler's annotation and two clock reads, so call sites never
    need to guard on whether observability is enabled.
    """

    def __init__(self, events=None, registry=None):
        self.events = events
        self.registry = registry
        self._local = threading.local()

    def _stack(self) -> list[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @property
    def depth(self) -> int:
        return len(self._stack())

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time a scope.  ``attrs`` must be host values (ints, floats,
        strings) — passing a jax.Array here would defeat the no-sync
        guarantee at serialization time."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        stack.append(name)
        with _annotation(name, attrs):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                t1 = time.perf_counter()
                stack.pop()
                if self.events is not None:
                    self.events.emit(
                        "span",
                        name=name,
                        dur_s=round(t1 - t0, 6),
                        depth=len(stack),
                        parent=parent,
                        **attrs,
                    )
                if self.registry is not None:
                    self.registry.histogram(
                        f"span_{name.replace('.', '_')}_s"
                    ).observe(t1 - t0)


_process_tracer = Tracer()


def get_tracer() -> Tracer:
    """The process's tracer: the one an entry point installed
    (``set_tracer``), else an annotation-only default — library code spans
    through it without asking whether observability is on."""
    return _process_tracer


def set_tracer(tracer: Tracer | None) -> Tracer:
    """Install ``tracer`` as the process's (``None``: a fresh default)."""
    global _process_tracer
    _process_tracer = tracer if tracer is not None else Tracer()
    return _process_tracer
