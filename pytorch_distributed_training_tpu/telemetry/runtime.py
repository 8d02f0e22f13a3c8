"""The Telemetry facade: one object the Runner (and bench) drives.

Bundles the process registry, a configured span recorder, the goodput
tracker, the jit-cache probe, the on-demand profiler, and the export
sinks behind the handful of calls the training loop makes:

    tel = Telemetry(dir=..., host=rank, is_rank0=..., tb_writer=...)
    with tel.span("data_wait", step=it): ...
    tel.note_step(dt, applied=..., replayed=...)
    tel.after_step(it, sync=state)      # probe poll + capture + export
    tel.diagnostics()                   # watchdog / peer-loss dump payload
    tel.close(step=final)               # final snapshot + summary + flush

``enabled=False`` keeps the full surface but turns every call into a
cheap no-op (spans become ``nullcontext``), so call sites never branch.
The registry itself stays live either way — recovery counters predate
this layer and must keep flowing (``engine/fault.py`` tests).
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
from typing import Dict, List, Optional

from .capture import OnDemandProfiler
from .goodput import GoodputTracker
from .registry import get_registry
from .retrace import get_probe
from .sinks import JsonlSink, LogSink, Sink, TensorBoardSink, summary_table
from .spans import SpanRecorder, set_recorder

__all__ = ["Telemetry"]


class Telemetry:
    """Per-run telemetry driver over the process-global instruments."""

    def __init__(
        self,
        enabled: bool = True,
        dir: Optional[str] = None,
        host: int = 0,
        is_rank0: bool = True,
        snapshot_interval: int = 100,
        span_ring: int = 256,
        retrace_warn: int = 3,
        tb_writer=None,
        use_tensorboard: bool = True,
        capture_signal: Optional[int] = None,
        capture_iters: int = 5,
        capture_at_iter: Optional[int] = None,
        capture_dir: Optional[str] = None,
        capture_python_tracer: bool = False,
        logger: Optional[logging.Logger] = None,
    ):
        self.enabled = bool(enabled)
        self.dir = dir
        self._logger = logger or logging.getLogger(__name__)
        self._interval = max(int(snapshot_interval), 1)
        self.registry = get_registry()
        self.goodput = GoodputTracker()
        self.probe = get_probe()
        self.probe.warn_threshold = int(retrace_warn)
        self.probe._logger = self._logger
        self.capture: Optional[OnDemandProfiler] = None
        self._sinks: List[Sink] = []
        self._recorder: Optional[SpanRecorder] = None
        self._closed = False
        if not self.enabled:
            return

        if dir is not None:
            os.makedirs(dir, exist_ok=True)
            span_path = os.path.join(dir, f"spans_rank{host}.jsonl")
        else:
            span_path = None
        # the configured recorder becomes the process-current one so deep
        # call sites (checkpoint writer thread, elastic guard) land in the
        # same ring/file (spans.span free function)
        self._recorder = set_recorder(
            SpanRecorder(path=span_path, ring=span_ring, host=host)
        )

        if is_rank0:
            if use_tensorboard and tb_writer is not None:
                self._sinks.append(TensorBoardSink(tb_writer))
            if dir is not None:
                self._sinks.append(
                    JsonlSink(os.path.join(dir, "snapshots.jsonl"))
                )
            self._sinks.append(LogSink(self._logger))

        cap_dir = capture_dir or (
            None if dir is None else os.path.join(dir, "profile")
        )
        if cap_dir is not None and (
            capture_signal is not None or capture_at_iter is not None
        ):
            self.capture = OnDemandProfiler(
                cap_dir,
                n_iters=capture_iters,
                signum=capture_signal,
                at_iter=capture_at_iter,
                python_tracer=capture_python_tracer,
                logger=self._logger,
            )

    # --------------------------------------------------------------- loop API
    def span(self, kind: str, step: Optional[int] = None, **extra):
        if not self.enabled or self._recorder is None:
            return contextlib.nullcontext()
        return self._recorder.span(kind, step=step, **extra)

    def note_step(self, seconds: float, applied: bool = True,
                  replayed: bool = False) -> None:
        if self.enabled:
            self.goodput.note_step(seconds, applied=applied, replayed=replayed)

    def note_lost(self, kind: str, seconds: float) -> None:
        if self.enabled:
            self.goodput.note_lost(kind, seconds)

    def after_step(self, it: int, sync=None) -> None:
        """Once per loop iteration: poll the retrace probe, advance any
        profiler capture window, and export on the snapshot interval."""
        if not self.enabled:
            return
        self.probe.poll(self.registry)
        if self.capture is not None:
            self.capture.after_step(it, sync=sync)
        if (it + 1) % self._interval == 0:
            self.export(it)

    # -------------------------------------------------------------- snapshots
    def snapshot(self) -> Dict:
        snap = self.registry.snapshot()
        snap["goodput"] = self.goodput.snapshot()
        snap["compiles"] = self.probe.snapshot()
        return snap

    def export(self, step: Optional[int]) -> Dict:
        snap = self.snapshot()
        for sink in self._sinks:
            try:
                sink.emit(snap, step)
            except Exception:  # one broken sink must not stop the others
                self._logger.exception(
                    "telemetry sink %s failed", type(sink).__name__
                )
        return snap

    def summary(self) -> str:
        """The human table (printed at end of run and on diagnostics)."""
        return summary_table(self.snapshot())

    def diagnostics(self, n_spans: int = 20) -> str:
        """Watchdog/peer-loss payload: last spans + the counter snapshot —
        what the process was doing, not just that it stopped."""
        spans = self._recorder.recent(n_spans) if self._recorder else []
        lines = ["last %d span(s):" % len(spans)]
        for rec in spans:
            lines.append("  " + json.dumps(rec))
        lines.append("registry summary:")
        lines.append(self.summary())
        return "\n".join(lines)

    # --------------------------------------------------------------- teardown
    def flush(self) -> None:
        """Crash-path flush: spans buffered to disk, nothing closed."""
        if self._recorder is not None:
            self._recorder.flush()

    def close(self, step: Optional[int] = None) -> None:
        """Final export + summary, then release files and the recorder."""
        if self._closed or not self.enabled:
            return
        self._closed = True
        self.probe.poll(self.registry)
        self.export(step)
        for sink in self._sinks:
            try:
                sink.close()
            except Exception:  # pragma: no cover - teardown best-effort
                pass
        if self.capture is not None:
            self.capture.close()
        if self._recorder is not None:
            self._recorder.close()
            set_recorder(None)  # restore the ring-only default
