"""Host spans kept in memory: what a program's host code was doing, when.

A :class:`Recorder` belongs to the object whose host code it follows (a
``models.shallow_water.SolverJob`` owns one and hands it to every save
it starts).  ``recorder.span(name, ...)`` is a context manager: it reads
``time.perf_counter_ns()`` on entry and on exit and keeps one
:class:`Span`, and it is a ``jax.profiler.TraceAnnotation`` of the same
name under the recorder's prefix, so a run wrapped in
``jax.profiler.trace(dir)`` shows the same spans on the profiler's own
timeline beside the device's operations.  Always on: nothing turns it
off, and the list is bounded instead (the newest ``bound`` spans stay,
``dropped`` counts those that went).

    with recorder.span("job/fetch", key=step, bytes=n) as fetch:
        ...
    waited_s += fetch.seconds
"""

import collections
import dataclasses
import itertools
import threading
import time

import jax

# Every span of a whole run of the benchmark's restarted job with room
# to spare: 1,219 a save of 606 pieces (a fetch and a write a piece),
# 1,215 a resume, about 10,000 on the window's job by the end of a run.
BOUND = 1 << 15


@dataclasses.dataclass(slots=True)
class Span:
    """One span, and the ``with`` that times it.  ``thread``: the name
    of the thread it ran on.  ``cause``: the ``id`` of the span that
    caused it: the span open on the same thread when it was made, or,
    across threads, the one that handed the work over.  ``key``: what
    the spans of one request share (a job's: the model step a snapshot
    or a save holds).  ``counts``: whatever else its caller gave
    (``bytes``, ``calls``, ``program``)."""

    name: str
    id: int
    thread: str
    cause: int
    key: object
    counts: dict
    start_ns: int = 0
    end_ns: int = 0
    _recorder: object = dataclasses.field(default=None, repr=False)
    _annotation: object = dataclasses.field(default=None, repr=False)

    @property
    def seconds(self):
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self):
        self._annotation.__enter__()
        self._recorder._stack().append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        recorder, annotation = self._recorder, self._annotation
        self._recorder = self._annotation = None
        recorder._stack().pop()
        recorder._keep(self)
        return annotation.__exit__(*exc)


class Recorder:
    def __init__(self, prefix="", bound=BOUND):
        self.prefix, self.bound = prefix, bound
        self.dropped = 0
        self._spans = collections.deque(maxlen=bound)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()

    def span(self, name, key=None, cause=None, **counts):
        """A :class:`Span` to be entered by ``with``, which gives it
        back.  ``cause`` defaults to the span open on this thread."""
        stack = self._stack()
        if cause is None and stack:
            cause = stack[-1].id
        return Span(
            name, next(self._ids), threading.current_thread().name, cause, key,
            counts, _recorder=self, _annotation=jax.profiler.TraceAnnotation(
                self.prefix + name, key=key, **counts))

    def spans(self):
        """The finished spans, in the order they ended."""
        with self._lock:
            return list(self._spans)

    def _stack(self):  # the spans open on this thread, outermost first
        return self._local.__dict__.setdefault("stack", [])

    def _keep(self, span):
        with self._lock:
            self.dropped += len(self._spans) == self.bound
            self._spans.append(span)
