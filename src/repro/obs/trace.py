"""Span tracer: host-side timed regions as nested spans with ids.

``SpanTracer.span("epoch_chunk", epochs=4)`` times a region on
``time.perf_counter`` and emits ONE event at exit (``type="span"`` with
``t0``/``dur_s``/``depth``/``id``/``parent``/``solve``), so a span costs
two clock reads plus one sink append.  A span is entered either with
``with`` or by hand (``__enter__``/``__exit__``, as the engine's chunk
loop does so that its obs-off path allocates nothing).

Ids: each span takes the tracer's next integer ``id`` at entry and records
``parent``, the id of the span it was opened in (None at the top), and
``solve``, the id of the enclosing ``REQUEST_SPAN`` (its own id for that
span; None outside one) — the shared request id of one ``engine.solve``
call.  A span that exits while children it opened are still open (a raise
skipped their exits) closes them first, innermost first, so the nesting
stack never outlives the region that owns it.

``jax_annotations=True`` additionally enters a
``jax.profiler.TraceAnnotation(name, id=, parent=, solve=)`` for the
span's duration.  Under an active profiler session it lands in the
profiler's own trace, on the clock the device ops share, as a host event
with the bare span name and those ids as stats; the JSONL span and the
trace event then join by ``id``, and any joined pair gives the offset
between the two clocks.  Without a session it is a no-op, and it degrades
silently when the profiler API is unavailable.  ``gc_annotation`` is the
matching hook for collector pauses (``python_gc``), registered by
``RunRecorder``.
"""

from __future__ import annotations

import time

#: the span one ``engine.solve`` call opens: every span inside it carries
#: its id as ``solve``
REQUEST_SPAN = "solve"


def _trace_annotation(name: str, **stats):
    """``jax.profiler.TraceAnnotation`` when available, else None."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name, **stats)
    except Exception:
        return None


def gc_annotation():
    """A ``gc.callbacks`` hook that brackets each collector pause in a
    ``python_gc`` TraceAnnotation (opened on "start", closed on "stop"),
    so a pause inside a traced region is named in the profiler's trace
    instead of being charged to the span it interrupted."""
    open_: list = []

    def hook(phase, info):
        if phase == "start":
            ann = _trace_annotation("python_gc",
                                    generation=info["generation"])
            if ann is not None:
                ann.__enter__()
                open_.append(ann)
        elif open_:
            open_.pop().__exit__(None, None, None)

    return hook


class Span:
    """One timed region of a ``SpanTracer``; ``id``/``parent``/``solve``
    are set when it is entered."""

    __slots__ = ("_tracer", "name", "attrs", "id", "parent", "solve",
                 "_depth", "_ann", "_t0")

    def __init__(self, tracer: "SpanTracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.id = self.parent = self.solve = None
        self._ann = None

    def __enter__(self):
        tr = self._tracer
        stack = tr._stack
        top = stack[-1] if stack else None
        self.id = tr._next_id
        tr._next_id += 1
        self.parent = top.id if top is not None else None
        self.solve = (self.id if self.name == REQUEST_SPAN
                      else top.solve if top is not None else None)
        if tr._jax:
            ids = {k: v for k, v in (("id", self.id),
                                     ("parent", self.parent),
                                     ("solve", self.solve))
                   if v is not None}
            self._ann = _trace_annotation(self.name, **ids)
            if self._ann is not None:
                self._ann.__enter__()
        self._depth = len(stack)
        stack.append(self)
        self._t0 = tr._clock()
        return self

    def set(self, **attrs):
        """Add ``attrs`` to an entered span: they ride in its event and, as
        stats, on its trace annotation (e.g. the backend ``solve_setup``
        resolved, known only inside the span)."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def __exit__(self, *exc):
        tr = self._tracer
        stack = tr._stack
        # children a raise left open close with (just before) their parent
        while stack[-1] is not self:
            stack[-1].__exit__(None, None, None)
        dur = tr._clock() - self._t0
        stack.pop()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if tr._sink is not None:
            tr._sink.record(type="span", name=self.name,
                            t0=self._t0 - tr.epoch0, dur_s=dur,
                            depth=self._depth, id=self.id,
                            parent=self.parent, solve=self.solve,
                            **({"attrs": self.attrs} if self.attrs else {}))
        return False


class SpanTracer:
    """Nested timed regions over one monotonic clock.

    ``sink`` is anything with ``record(type=..., **fields)`` (a
    ``RunRecorder``); with no sink the spans still time and nest but emit
    nowhere (cheap standalone use).  ``clock`` is injectable for tests.
    """

    def __init__(self, sink=None, *, clock=time.perf_counter,
                 jax_annotations: bool = False):
        self._sink = sink
        self._clock = clock
        self._jax = jax_annotations
        self._stack: list = []
        self._next_id = 0
        #: origin of the tracer's relative timeline (t0 fields are offsets
        #: from this, so JSONL stays small and runs are comparable)
        self.epoch0 = clock()

    @property
    def depth(self) -> int:
        return len(self._stack)

    def span(self, name: str, **attrs) -> Span:
        """A region to time; emits one span event at exit.

        ``attrs`` ride along verbatim (epoch counts, byte counts, worker
        ids) — keep them JSON-serializable.
        """
        return Span(self, name, attrs)
