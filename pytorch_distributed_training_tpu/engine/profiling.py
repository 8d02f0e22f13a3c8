"""Config-gated ``jax.profiler`` trace hooks.

The reference has no profiling subsystem at all (SURVEY.md §5.1: only tqdm
progress bars + an ineffective ``cudnn.benchmark`` toggle); the rebuild adds
the TPU-native one: an XLA trace window captured with ``jax.profiler`` that
can be opened in TensorBoard / Perfetto (HLO timelines, per-op HBM + MXU
utilization).  Config-gated so default behavior matches the reference:

.. code-block:: yaml

    training:
      profile:
        dir: run/profile     # trace output directory (required)
        start_iter: 10       # window opens after this iteration completes,
                             # so iterations start_iter+1 .. start_iter+n_iters
                             # are traced (default 10: skips the XLA-compile
                             # iterations, which would dwarf the timeline)
        n_iters: 5           # number of traced iterations (default 5)
        python_tracer: false # python frames in the trace; off: the program's
                             # spans (telemetry/spans.py) name the host's
                             # time, and the frames slow what they measure

The Runner calls :meth:`after_step` once per iteration on the rank-0 host
only.  Validation and checkpoint I/O force-close the window so only
steady-state train steps land in the trace; if that close happens before any
traced iteration completed, the window re-arms and retries after the
interruption (a partial window logs a warning instead).
"""
from __future__ import annotations

import logging
from collections.abc import Mapping
from typing import Any, Dict, Optional

from ..telemetry.capture import start_trace

__all__ = ["TraceProfiler"]


class TraceProfiler:
    """One bounded ``jax.profiler`` trace window over the training loop."""

    def __init__(self, directory: str, start_iter: int = 10, n_iters: int = 5,
                 python_tracer: bool = False,
                 logger: Optional[logging.Logger] = None):
        if n_iters <= 0:
            raise ValueError(f"profile.n_iters must be positive, got {n_iters}")
        self.directory = directory
        self.start_iter = int(start_iter)
        self.n_iters = int(n_iters)
        self.python_tracer = bool(python_tracer)
        self._active = False
        self._done = False
        self._log = logger or logging.getLogger(__name__)

    @classmethod
    def from_config(
        cls, train_cfg: Dict[str, Any], logger: Optional[logging.Logger] = None
    ) -> Optional["TraceProfiler"]:
        """Build from the ``training.profile`` config section (None if absent)."""
        prof_cfg = train_cfg.get("profile")
        if prof_cfg is None or prof_cfg is False:
            return None
        # an empty mapping is a *misconfiguration* (user enabled the section
        # but gave no keys) — fall through so the 'dir' check raises
        if not isinstance(prof_cfg, Mapping):
            raise ValueError(
                f"training.profile must be a mapping with a 'dir' key, got {prof_cfg!r}"
            )
        if "dir" not in prof_cfg:
            raise ValueError("training.profile.dir is required when profiling is enabled")
        return cls(
            directory=prof_cfg["dir"],
            start_iter=prof_cfg.get("start_iter", 10),
            n_iters=prof_cfg.get("n_iters", 5),
            python_tracer=prof_cfg.get("python_tracer", False),
            logger=logger,
        )

    def after_step(self, iteration: int, sync=None) -> None:
        """Open/close the trace window; called once AFTER each iteration, so
        opening when ``iteration == start_iter`` traces iterations
        ``start_iter+1 .. start_iter+n_iters`` inclusive.

        ``sync``: optional pytree of device arrays (e.g. the train state) to
        ``block_until_ready`` on at the window boundaries — required for the
        trace to actually contain the device timeline, since the steady-state
        loop never otherwise syncs (JAX dispatch is async; without the barrier
        ``stop_trace`` could fire while the traced steps are still enqueued).
        Blocking happens only at the two boundary crossings, not per step.
        """
        import jax

        if self._done:
            return
        if not self._active and iteration >= self.start_iter:
            if sync is not None:
                jax.block_until_ready(sync)  # keep prior async work out of the window
            start_trace(self.directory, python_tracer=self.python_tracer)
            self._active = True
            self._opened_at = iteration
            self._last_step = iteration
            self._log.info("profiler: trace started after iter %d -> %s",
                           iteration, self.directory)
        elif self._active:
            self._last_step = iteration
            if iteration >= self._opened_at + self.n_iters:
                self.stop(sync=sync)

    def stop(self, sync=None) -> None:
        """Close the window if open — also called before validation/checkpoint
        work so only steady-state train iterations land in the trace.  An early
        close that captured ZERO iterations discards the window and re-arms it
        (retry after the interruption); a partial capture logs a warning."""
        import jax

        if not self._active:
            return
        if sync is not None:
            jax.block_until_ready(sync)
        jax.profiler.stop_trace()
        self._active = False
        captured = self._last_step - self._opened_at
        if captured <= 0:
            # e.g. validation fired at the very iteration the window opened:
            # nothing traced yet — wait for the next quiet iteration instead
            self._log.warning(
                "profiler: window closed before any iteration was traced; "
                "re-arming (will retry after the interruption)"
            )
            return
        self._done = True
        if captured < self.n_iters:
            self._log.warning(
                "profiler: window closed early: %d of %d iterations captured -> %s",
                captured, self.n_iters, self.directory,
            )
        else:
            self._log.info("profiler: trace stopped -> %s", self.directory)

    def finalize(self) -> None:
        """Loop-exit hook: close any open window and warn if the configured
        window never produced a trace (e.g. ``start_iter >= train_iters``)."""
        self.stop()
        if not self._done:
            self._log.warning(
                "profiler: no trace captured (start_iter=%d never reached or "
                "every window was interrupted) -> %s",
                self.start_iter, self.directory,
            )
