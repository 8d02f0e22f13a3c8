"""On-demand profiler capture: a bounded jax.profiler window, on request.

The existing :class:`..engine.profiling.TraceProfiler` captures a window
configured BEFORE launch (``training.profile``).  Production regressions
don't schedule themselves: this module arms a capture while the run is
already going — either

- **signal-triggered**: ``kill -USR2 <pid>`` latches a flag (the handler
  does nothing else — signal-safe), and the NEXT step boundary opens a
  ``jax.profiler`` trace for ``n_iters`` steps into the telemetry dir; or
- **config-triggered**: ``training.telemetry.capture.at_iter`` arms the
  same window at a fixed step, for reproducing a known-bad region.

The window is bounded and closes itself (step-granular, synced on the
state so the trace ends at a step boundary, mirroring TraceProfiler's
hygiene).  One capture at a time; re-signalling during a capture is
ignored.  Signal installation only happens on the main thread (Python
refuses elsewhere) and restores the previous handler on ``close``.
"""
from __future__ import annotations

import logging
import os
import signal
import threading
import time
from typing import Optional

__all__ = ["OnDemandProfiler", "parse_signal", "start_trace"]


def start_trace(directory: str, python_tracer: bool = False) -> None:
    """Start a ``jax.profiler`` trace, for both of the program's windows
    (this module's and ``engine/profiling.py``'s).  The python tracer is
    off unless asked for: the program's spans are annotations in the trace
    (telemetry/spans.py) and name a gap without python frames, and the
    frames are dear (PERF.md, PR 23: a serving tick of 5.5 ms read 8.6 to
    11.6 ms under it; stopping a 1.4 s trace took 22 s under sixteen loader
    threads)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1 if python_tracer else 0
    jax.profiler.start_trace(directory, profiler_options=options)


def parse_signal(spec) -> Optional[int]:
    """``"SIGUSR2"`` / ``"USR2"`` / ``12`` / None -> signal number."""
    if spec is None:
        return None
    if isinstance(spec, int):
        return signal.Signals(spec).value
    name = str(spec).upper()
    if not name.startswith("SIG"):
        name = "SIG" + name
    try:
        return signal.Signals[name].value
    except KeyError:
        raise ValueError(
            f"unknown capture signal {spec!r} (want e.g. SIGUSR2 or a number)"
        ) from None


class OnDemandProfiler:
    """Armable bounded jax.profiler window (signal- or config-triggered)."""

    def __init__(
        self,
        trace_dir: str,
        n_iters: int = 5,
        signum: Optional[int] = None,
        at_iter: Optional[int] = None,
        python_tracer: bool = False,
        logger: Optional[logging.Logger] = None,
    ):
        if int(n_iters) < 1:
            raise ValueError(f"capture n_iters must be >= 1, got {n_iters}")
        self.trace_dir = trace_dir
        self.n_iters = int(n_iters)
        self.at_iter = None if at_iter is None else int(at_iter)
        self.signum = signum
        self.python_tracer = bool(python_tracer)
        self._logger = logger or logging.getLogger(__name__)
        self._armed = threading.Event()
        self._tracing_from: Optional[int] = None
        self._captures = 0
        self._prev_handler = None
        self._installed = False
        if signum is not None and threading.current_thread() is threading.main_thread():
            self._prev_handler = signal.signal(signum, self._on_signal)
            self._installed = True

    # signal context: just latch the flag — everything else happens at the
    # next step boundary on the training thread
    def _on_signal(self, signum, frame) -> None:  # pragma: no cover - handler
        self._armed.set()

    def arm(self) -> None:
        """Programmatic trigger (the config path and tests)."""
        self._armed.set()

    @property
    def tracing(self) -> bool:
        return self._tracing_from is not None

    def after_step(self, it: int, sync=None) -> None:
        """Step-boundary hook: open an armed window / close a full one."""
        if self._tracing_from is not None:
            if it + 1 - self._tracing_from >= self.n_iters:
                self._stop(sync)
            return
        if self.at_iter is not None and it + 1 == self.at_iter:
            self._armed.set()
        if self._armed.is_set():
            self._armed.clear()
            self._start(it + 1)

    def _start(self, from_iter: int) -> None:
        out = os.path.join(
            self.trace_dir, f"capture_{self._captures}_iter{from_iter}"
        )
        os.makedirs(out, exist_ok=True)
        try:
            start_trace(out, python_tracer=self.python_tracer)
        except Exception as e:
            # a second live trace in the process (e.g. TraceProfiler's
            # window) raises — skip this capture rather than kill the run
            self._logger.warning("on-demand capture could not start: %s", e)
            return
        self._tracing_from = from_iter
        self._t0 = time.monotonic()
        self._logger.warning(
            "on-demand profiler capture ON: steps %d..%d -> %s",
            from_iter, from_iter + self.n_iters - 1, out,
        )

    def _stop(self, sync=None) -> None:
        import jax

        if sync is not None:
            jax.block_until_ready(sync)
        jax.profiler.stop_trace()
        self._logger.warning(
            "on-demand profiler capture done: %d step(s) in %.2fs",
            self.n_iters, time.monotonic() - self._t0,
        )
        self._tracing_from = None
        self._captures += 1

    def close(self, sync=None) -> None:
        if self._tracing_from is not None:
            try:
                self._stop(sync)
            except Exception:  # pragma: no cover - teardown best-effort
                pass
        if self._installed:
            try:
                signal.signal(self.signum, self._prev_handler)
            except (ValueError, TypeError):  # pragma: no cover - non-main thread
                pass
            self._installed = False
