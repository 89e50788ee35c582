"""The measured window and the traced part of it.

The window opens at a step's completion and closes at the first step
completion at least ``seconds`` later, so it covers whole iterations
of the program's own loop. With ``trace`` the profiler records its
first ``TRACE_SECONDS`` (or the whole window, if shorter) under a
``bench.window`` span; the window itself goes on to ``seconds`` either
way, so a traced run compares the same answers as an untraced one.
"""
from __future__ import annotations

import time
from typing import Optional

TRACE_SECONDS = 4.0


class WindowClosed(Exception):
    """Raised from inside the program's loop when the window closes, so
    the loop ends without the work it does at a clean exit."""


class Window:
    def __init__(self, seconds: float, trace_dir: Optional[str],
                 t_process: float):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.t_process = t_process
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.setup_s: Optional[float] = None
        self.tracing = False
        self.traced: Optional[tuple] = None     # (t0, t1) host clock
        self._span = None

    @property
    def open(self) -> bool:
        return self.t_open is not None and self.t_close is None

    def start(self, t: float) -> None:
        self.t_open = t
        self.setup_s = t - self.t_process
        if self.trace_dir:
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.tracing = True
            self._t_trace = time.perf_counter()

    def tick(self, t: float) -> None:
        """After a step that completed at ``t``: stop tracing and close
        the window when their time is up (closing raises)."""
        if self.tracing and (t - self._t_trace >= TRACE_SECONDS
                             or t - self.t_open >= self.seconds):
            self.stop_trace(t)
        if t - self.t_open >= self.seconds:
            self.t_close = t
            raise WindowClosed()

    def stop_trace(self, t: float) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False
        self.traced = (self._t_trace, t)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open
