"""Host-phase trace spans: where each step's wall-clock actually went.

A span brackets one host-visible phase of the training loop — data wait,
step dispatch, device block, eval, checkpoint snapshot vs async write,
elastic guard window — with a context manager:

    with spans.span("data_wait", step=it):
        batch = next(stream)

Every span records monotonic start, wall-clock start, duration, kind,
step, and thread, and lands in two places:

- a bounded in-memory ring (always on, O(1) per span) that the watchdog
  and peer-loss diagnostics dump — a hang report says what the process was
  DOING, not just that it stopped;
- optionally a per-host JSONL file (``spans_rank<k>.jsonl`` under the
  telemetry dir), append-buffered and flushed every ``flush_every`` spans
  so the file cost stays off the per-span path.

Per-host files rather than one shared file: hosts only share a filesystem
by accident, and interleaved writers corrupt JSONL.  Rank 0's periodic
registry snapshot (sinks.py) is the aggregated view.

The module keeps one *current* recorder that the free function
:func:`span` uses, so deep call sites (``engine/checkpoint.py``'s writer
thread, ``engine/elastic.py``'s guard) emit spans without threading a
handle through every constructor — the same pattern as the fault-counter
ledger.  The default recorder is ring-only; the Runner swaps in its
configured recorder for the duration of the run.

One call, two sinks, one clock: while a span is open it also holds a
``jax.profiler.TraceAnnotation`` of the same name, with ``step`` and the
extra fields as its arguments, so that during a profiler trace every
program span lies on the host plane of the ``.xplane.pb`` on the
profiler's own clock, beside the device's operations (with no trace
running the annotation costs a few hundred nanoseconds).  The class is
taken lazily and only from a ``jax`` that is already loaded: this module
stays stdlib-only for the loader's worker processes, which never import
JAX and whose spans go to the ring alone.

A record names its cause: ``parent`` is the kind of the span that was
open on the same thread when this one started (``loader_wait`` and
``h2d_put`` inside ``data_wait``; a tick's phases inside ``tick``), null
at the top.  Nesting is a property of the thread, not of a recorder, so
the stack is one thread-local for the module.

``span(..., cpu=True)`` also reads the thread's own CPU clock
(``time.thread_time()``) at both ends and records ``cpu_ms``: a thread
blocked on the device, a lock or a core burns no CPU time, so wall less
``cpu_ms`` is what the thread spent OFF the CPU.  A close-time field: the
profiler's annotation takes its arguments at enter, so ``cpu_ms`` is in the
ring and the span file and not in a trace.
"""
from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

__all__ = ["SpanRecorder", "get_recorder", "record", "set_recorder", "span"]

_OPEN = threading.local()  # .kinds: this thread's stack of open span kinds
_NO_SPAN = contextlib.nullcontext()  # what a disabled recorder hands out


def _open_kinds() -> List[str]:
    try:
        return _OPEN.kinds
    except AttributeError:
        _OPEN.kinds = []
        return _OPEN.kinds


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` once ``jax`` is loaded in this
    process, else None; never imports it."""
    jax = sys.modules.get("jax")
    return getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)


class _Span:
    """One open span: a plain class, not a generator, because the
    scheduler opens several a tick."""

    __slots__ = ("_recorder", "_kind", "_step", "_extra", "_parent",
                 "_annotation", "_t0", "_wall", "_cpu0")

    def __init__(self, recorder, kind, step, extra, cpu=False):
        self._recorder = recorder
        self._kind = kind
        self._step = step
        self._extra = extra
        self._cpu0 = cpu  # False, or from __enter__ on the thread's CPU clock

    def __enter__(self):
        kinds = _open_kinds()
        self._parent = kinds[-1] if kinds else None
        kinds.append(self._kind)
        cls = _annotation_class()
        if cls is None:
            self._annotation = None
        else:
            args = {k: v for k, v in self._extra.items() if v is not None}
            if self._step is not None:
                args["step"] = self._step
            self._annotation = cls(self._kind, **args)
            self._annotation.__enter__()
        self._wall = time.time()
        self._t0 = time.monotonic()
        if self._cpu0 is not False:
            self._cpu0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        if self._cpu0 is not False:
            cpu_ms = (time.thread_time() - self._cpu0) * 1e3
            self._extra = dict(self._extra, cpu_ms=round(cpu_ms, 3))
        dur_s = time.monotonic() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _open_kinds().pop()
        self._recorder._record(
            self._kind, self._step, self._t0, self._wall, dur_s,
            self._parent, self._extra,
        )
        return False


class SpanRecorder:
    """Thread-safe span sink: bounded ring + optional buffered JSONL file."""

    def __init__(
        self,
        path: Optional[str] = None,
        ring: int = 256,
        host: int = 0,
        flush_every: int = 64,
    ):
        self.path = path
        self.host = int(host)
        self._ring: deque = deque(maxlen=max(int(ring), 1))
        self._buf: List[str] = []
        self._flush_every = max(int(flush_every), 1)
        self._lock = threading.Lock()
        self._file = open(path, "a") if path else None
        self.enabled = True

    def span(self, kind: str, step: Optional[int] = None, cpu: bool = False,
             **extra):
        """Context manager around one phase; ``extra`` fields (``req``,
        ``n``, ``bytes``...) ride in the record and in the annotation.
        ``cpu=True`` adds ``cpu_ms``, the thread's CPU time inside the
        span, to the record alone."""
        if not self.enabled:
            return _NO_SPAN
        return _Span(self, kind, step, extra, cpu)

    def record(self, kind: str, t0: float, dur_s: float,
               step: Optional[int] = None, **extra) -> None:
        """An interval that is already over, from its ``time.monotonic()``
        start (a request's life, known only at its retirement).  Ring and
        file only: the profiler takes no back-dated annotation."""
        if self.enabled:
            wall = time.time() - (time.monotonic() - t0)
            self._record(kind, step, t0, wall, dur_s, None, extra)

    def _record(self, kind, step, t0, wall, dur_s, parent, extra) -> None:
        rec: Dict = {
            "kind": kind,
            "step": step,
            "parent": parent,
            "host": self.host,
            "t": round(t0, 6),
            "wall": round(wall, 3),
            "ms": round(dur_s * 1e3, 3),
            "thread": threading.current_thread().name,
        }
        if extra:
            rec.update(extra)
        with self._lock:
            self._ring.append(rec)
            if self._file is not None:
                self._buf.append(json.dumps(rec))
                if len(self._buf) >= self._flush_every:
                    self._flush_locked()

    def _flush_locked(self) -> None:
        if self._buf and self._file is not None:
            self._file.write("\n".join(self._buf) + "\n")
            self._file.flush()
        self._buf.clear()

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def recent(self, n: Optional[int] = None) -> List[Dict]:
        """Last ``n`` spans, oldest first (diagnostics payload)."""
        with self._lock:
            items = list(self._ring)
        return items if n is None else items[-int(n):]

    def close(self) -> None:
        with self._lock:
            self._flush_locked()
            if self._file is not None:
                self._file.close()
                self._file = None


# ----------------------------------------------------------- current recorder
_LOCK = threading.Lock()
_RECORDER: Optional[SpanRecorder] = None


def get_recorder() -> SpanRecorder:
    """The current recorder (a ring-only default until a run installs one)."""
    global _RECORDER
    if _RECORDER is None:
        with _LOCK:
            if _RECORDER is None:
                _RECORDER = SpanRecorder()
    return _RECORDER


def set_recorder(recorder: Optional[SpanRecorder]) -> SpanRecorder:
    """Install ``recorder`` as the process's current one (None restores a
    fresh ring-only default); returns the recorder now in effect."""
    global _RECORDER
    with _LOCK:
        _RECORDER = recorder if recorder is not None else SpanRecorder()
        return _RECORDER


def span(kind: str, step: Optional[int] = None, **extra):
    """Record a phase span on the current recorder (context manager)."""
    return get_recorder().span(kind, step=step, **extra)


def record(kind: str, t0: float, dur_s: float,
           step: Optional[int] = None, **extra) -> None:
    """Record a finished interval on the current recorder."""
    get_recorder().record(kind, t0, dur_s, step=step, **extra)
