"""Low-overhead event recorder: the shared clock of the serve + fed stacks.

One ``Recorder`` instance is the single timeline for everything a process
does — serving steps, federated rounds, page churn, wire traffic — so a
Chrome-trace export lines every subsystem up against one monotonic clock
instead of each bench keeping its own ``time.perf_counter()`` deltas.
The ``clock-discipline`` pass in :mod:`repro.analysis` (tier-1) flags
real raw-clock *call sites* anywhere in ``src/repro`` — this file is
the allowlisted clock owner, the one place that touches ``time``.

Design constraints, in order:

* **A disabled recorder is a true no-op.** ``NULL_RECORDER`` is a
  singleton whose methods do nothing and whose ``enabled`` is ``False``;
  its ``span`` reads no clock, and other timestamp reads sit behind
  ``if rec.enabled:``, so a recorder-free engine never calls the clock,
  never allocates an event, and never changes trace counts or dispatch
  behaviour.
* **Zero device work.** The recorder stores host scalars only
  (floats/ints/strings). It never touches device state; the one call
  into jax on the record path is the profiler's host-side annotation,
  and exporting is a pure host serialization — recording cannot add
  device dispatches by construction.
* **Append-only ring buffer.** Events land in a ``deque(maxlen=capacity)``
  — O(1) append, oldest events drop first under pressure (``dropped``
  counts them), no reallocation spikes mid-run.

Clock semantics: ``now()`` is ``time.perf_counter()`` — host-monotonic
seconds with an arbitrary origin, shared by every subsystem recording
into the same instance. Spans measure *host wall time between the two
reads*; they include device time exactly when the host blocks on the
result inside the span (the serve engine's step spans do — each step
materializes its logits — so step spans are true step latencies).

Event model (one tuple per event, Chrome-trace phase names)::

    ("X", name, track, t0, dur, args)   span      [t0, t0 + dur)
    ("i", name, track, t0, 0.0, args)   instant   at t0
    ("C", name, track, t0, 0.0, args)   counter sample (args = {series: value})

``track`` is a free-form string; the Chrome exporter maps each distinct
track to its own thread row (one per request, one per client, one per
engine/server). Within one track, spans are recorded by sequential host
code, so they never overlap — the export golden test pins that.

Profiler capture: ``span(name, track, **args)`` is the one way to time a
region. Whenever a ``jax.profiler`` capture is running it also opens a
``jax.profiler.TraceAnnotation(name)``, on ``Recorder`` and on
``NULL_RECORDER`` alike, so the region lands on the capture's host
timeline under the same name as in the ring buffer (names take the form
``<layer>.<what>``: ``fed.broadcast``, ``serve.decode_step``). With no
capture running and recording off a span costs one flag check and
records nothing. The span arguments go to the ring buffer only: an
annotation carrying them would change the event's name in the capture.
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import nullcontext
from typing import List, Tuple

from jax.profiler import TraceAnnotation

Event = Tuple[str, str, str, float, float, dict]

#: shared reusable+reentrant null context (contextlib documents
#: ``nullcontext`` instances as both), so a span with no capture running
#: and recording off costs one flag check and an empty
#: ``__enter__``/``__exit__``
_NULL_CTX = nullcontext()

#: True while a profiler capture is running (``TraceMe.is_enabled``);
#: a jaxlib without that query opens every annotation, which records
#: nothing outside a capture
_capture_running = getattr(TraceAnnotation, "is_enabled", lambda: True)


class _Span:
    """One region timed by an enabled recorder: a ring-buffer span, plus
    a profiler annotation while a capture runs. ``seconds`` holds the
    region's length once it has closed."""

    __slots__ = ("_rec", "_name", "_track", "_args", "_ann", "t0",
                 "seconds")

    def __init__(self, rec: "Recorder", name: str, track: str, args: dict):
        self._rec, self._name, self._track, self._args = \
            rec, name, track, args
        self.seconds = 0.0

    def __enter__(self) -> "_Span":
        self._ann = TraceAnnotation(self._name) if _capture_running() \
            else None
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.seconds = max(t1 - self.t0, 0.0)
        self._rec.complete(self._name, self._track, self.t0, t1,
                           **self._args)


class Recorder:
    """Append-only host-side event recorder over one monotonic clock."""

    __slots__ = ("enabled", "capacity", "appended", "_events")

    def __init__(self, capacity: int = 65536, annotate: bool = False):
        """``annotate`` is accepted and does nothing: every span reaches
        a running profiler capture whatever the recorder."""
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.enabled = True
        self.capacity = int(capacity)
        self.appended = 0                 # total ever, incl. dropped
        self._events: deque = deque(maxlen=self.capacity)

    # -- clock --------------------------------------------------------------

    @staticmethod
    def now() -> float:
        """Monotonic seconds (arbitrary origin, shared process-wide)."""
        return time.perf_counter()

    @staticmethod
    def wall() -> float:
        """Wall-clock seconds (``time.time()``) — NOT for recording.

        Events always carry ``now()`` stamps; the wall clock exists only
        for the cross-process clock handshake (``repro.obs.collect``),
        where it is the one reference two processes share. This is the
        single sanctioned wall-clock read in ``repro.obs`` — the raw-
        clock lint holds every other module to ``now()``/``wall()``.
        """
        return time.time()

    # -- recording ----------------------------------------------------------

    def instant(self, name: str, track: str, **args) -> None:
        self.appended += 1
        self._events.append(("i", name, track, time.perf_counter(), 0.0,
                             args))

    def complete(self, name: str, track: str, t0: float, t1: float,
                 **args) -> None:
        """A finished span from two ``now()`` reads, for events timed
        elsewhere (a child process, a test); code that times a region
        of its own uses ``span``."""
        self.appended += 1
        self._events.append(("X", name, track, t0, max(t1 - t0, 0.0),
                             args))

    def span(self, name: str, track: str, **args):
        """Time the ``with`` block: a span in the ring buffer while the
        recorder is enabled, and a ``TraceAnnotation(name)`` while a
        profiler capture runs. Entering yields the span, whose
        ``seconds`` is its length after the block (enabled only)."""
        if self.enabled:
            return _Span(self, name, track, args)
        return NULL_RECORDER.span(name, track)

    def counter_sample(self, name: str, track: str, value) -> None:
        """One sample of a named time series (Chrome 'C' event)."""
        self.appended += 1
        self._events.append(("C", name, track, time.perf_counter(), 0.0,
                             {name: value}))

    # -- introspection ------------------------------------------------------

    @property
    def dropped(self) -> int:
        """Events lost to ring-buffer pressure (oldest-first)."""
        return self.appended - len(self._events)

    def events(self) -> List[Event]:
        """Snapshot of the retained events, oldest first."""
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()
        self.appended = 0

    def __len__(self) -> int:
        return len(self._events)


class NullRecorder:
    """The disabled recorder: every method is a no-op, ``enabled`` is
    False, and there is exactly one instance (``NULL_RECORDER``) so
    'recording is off' is an identity check away."""

    __slots__ = ()
    enabled = False
    capacity = 0
    appended = 0
    dropped = 0

    @staticmethod
    def now() -> float:
        return 0.0

    def instant(self, name: str, track: str, **args) -> None:
        pass

    def complete(self, name: str, track: str, t0: float, t1: float,
                 **args) -> None:
        pass

    def span(self, name: str, track: str, **args):
        """Records nothing; a profiler annotation while a capture runs."""
        return TraceAnnotation(name) if _capture_running() else _NULL_CTX

    def counter_sample(self, name: str, track: str, value) -> None:
        pass

    def events(self) -> List[Event]:
        return []

    def clear(self) -> None:
        pass

    def __len__(self) -> int:
        return 0


NULL_RECORDER = NullRecorder()
