"""On-demand profile capture (ISSUE 7 tentpole #3).

Chip captures used to require editing a script to pass ``--profile`` and
re-running from step 0 — useless for "the run went slow an hour in,
grab me a trace NOW". This module opens a bounded
``jax.profiler.start_trace``/``stop_trace`` window *mid-run*, triggered
three ways:

* ``--traceSteps N@M`` — capture steps M..M+N-1 (planned ahead: the
  classic "skip warmup, profile the steady state" recipe);
* ``SIGUSR2`` — ``kill -USR2 <pid>`` opens a window of ``window_steps``
  at the next step boundary (works on a run launched with no profiling
  flags at all, as long as ``--traceDir`` gave captures a home);
* touch-file — ``touch <traceDir>/CAPTURE`` does the same from a shell
  that only shares a filesystem with the run (TPU pods behind a
  bastion). The file is consumed (removed) when the window opens, so
  one touch = one capture.

Every window lands in its own ``<trace_dir>/capture_<step>`` directory
and is VERIFIED on close: the resulting ``*.xplane.pb`` must parse with
``utils/xplane.parse_xspace`` (the PR 3 reader) — a capture that
silently wrote garbage is reported as failed, not discovered a day
later on a laptop without the chip.

The controller is driven by one ``on_step(step)`` call per dispatch;
call sites hold ``None`` when no capture is configured, so the
steady-state cost is a ``None`` check. With a controller installed but
idle, the cost is an int compare plus (touch-file mode) one ``stat``.
"""

from __future__ import annotations

import logging
import os
import re
import signal as _signal
import threading
from typing import List, Optional, Tuple

logger = logging.getLogger("bigdl_tpu")

__all__ = ["CaptureController", "parse_trace_steps", "TOUCH_FILE_NAME"]

TOUCH_FILE_NAME = "CAPTURE"

_SPEC_RE = re.compile(r"^(\d+)@(\d+)$")


def parse_trace_steps(spec: str) -> Tuple[int, int]:
    """``"N@M"`` -> ``(n_steps, start_step)``; steps are 0-indexed
    dispatch counts (M=0 captures from the first timed step)."""
    m = _SPEC_RE.match(str(spec).strip())
    if not m:
        raise ValueError(
            f"--traceSteps {spec!r}: expected N@M (capture N steps "
            f"starting at step M), e.g. 5@20")
    n, start = int(m.group(1)), int(m.group(2))
    if n < 1:
        raise ValueError(f"--traceSteps {spec!r}: N must be >= 1")
    return n, start


class CaptureController:
    """Bounded mid-run ``jax.profiler`` windows with post-close
    verification.

    ``captures`` (and :meth:`annotation`) records one dict per window:
    ``{start_step, stop_step, trigger, dir, xplane, planes, ok}`` plus
    ``error`` when the profiler or the verify failed — the failure mode
    is a reported bad capture, never a crashed training run.
    """

    def __init__(self, trace_dir: str, trace_steps: Optional[str] = None,
                 window_steps: int = 5, touch_file: Optional[str] = None,
                 install_signal: bool = True):
        self.trace_dir = str(trace_dir)
        os.makedirs(self.trace_dir, exist_ok=True)
        self._planned: Optional[Tuple[int, int]] = (
            parse_trace_steps(trace_steps) if trace_steps else None)
        self.window_steps = max(1, int(window_steps))
        self.touch_file = (touch_file if touch_file is not None
                           else os.path.join(self.trace_dir,
                                             TOUCH_FILE_NAME))
        self.captures: List[dict] = []
        self._active: Optional[dict] = None
        self._stop_at: int = 0
        self._signal_pending = False
        self._prev_handler = None
        if install_signal:
            self._install_signal()

    # ------------------------------------------------------------ triggers
    def _install_signal(self) -> None:
        def _handler(signum, frame):
            # flag only — start_trace from inside a signal handler could
            # land mid-dispatch; the next on_step boundary acts on it
            self._signal_pending = True

        try:
            if threading.current_thread() is threading.main_thread():
                self._prev_handler = _signal.signal(_signal.SIGUSR2,
                                                    _handler)
        except (ValueError, OSError, AttributeError):
            self._prev_handler = None  # non-main thread / platform quirk

    def request_capture(self) -> None:
        """Programmatic trigger (same path as SIGUSR2): open a
        ``window_steps`` window at the next step boundary."""
        self._signal_pending = True

    def _touch_triggered(self) -> bool:
        if not self.touch_file:
            return False
        if os.path.exists(self.touch_file):
            try:  # consume: one touch = one capture
                os.remove(self.touch_file)
            except OSError:
                pass
            return True
        return False

    # ---------------------------------------------------------------- steps
    def on_step(self, step: int) -> None:
        """One call per dispatch, BEFORE the step runs. Opens a pending
        window at its start step and closes+verifies an open window at
        its stop step."""
        if self._active is not None:
            if step >= self._stop_at:
                self._stop()
            else:
                return  # window still open; triggers wait for it
        if self._planned is not None and step >= self._planned[1]:
            n, start = self._planned
            self._planned = None
            self._start(step, step + n, trigger=f"traceSteps:{n}@{start}")
            return
        if self._signal_pending:
            self._signal_pending = False
            self._start(step, step + self.window_steps, trigger="signal")
            return
        if self._touch_triggered():
            self._start(step, step + self.window_steps, trigger="touch")

    def finish(self) -> None:
        """End-of-run drain: close a still-open window (a --traceSteps
        spec past the last step, or a trigger near the end)."""
        if self._active is not None:
            self._stop()
        if self._prev_handler is not None:
            try:
                _signal.signal(_signal.SIGUSR2, self._prev_handler)
            except (ValueError, OSError):
                pass
            self._prev_handler = None

    # --------------------------------------------------------------- window
    def _start(self, step: int, stop_at: int, trigger: str) -> None:
        d = os.path.join(self.trace_dir, f"capture_{step}")
        rec = {"start_step": step, "stop_step": stop_at,
               "trigger": trigger, "dir": d, "ok": False}
        try:
            import jax
            jax.profiler.start_trace(d)
        except Exception as e:  # a second profiler session, no backend...
            rec["error"] = f"start_trace: {type(e).__name__}: {e}"[:200]
            self.captures.append(rec)
            logger.warning("obs capture failed to open at step %d: %s",
                           step, rec["error"])
            return
        self._active = rec
        self._stop_at = stop_at
        logger.info("obs capture open at step %d (until %d, trigger=%s) "
                    "-> %s", step, stop_at, trigger, d)

    def _stop(self) -> None:
        rec, self._active = self._active, None
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:
            rec["error"] = f"stop_trace: {type(e).__name__}: {e}"[:200]
            self.captures.append(rec)
            logger.warning("obs capture failed to close: %s", rec["error"])
            return
        self._verify(rec)
        self.captures.append(rec)
        logger.info("obs capture closed: %s (ok=%s, %s planes)",
                    rec["dir"], rec["ok"], rec.get("planes"))

    def _verify(self, rec: dict) -> None:
        """A capture only counts if the PR 3 reader can parse it — the
        whole point of on-demand capture is a trace someone can read."""
        from bigdl_tpu.utils.xplane import find_xplane_pb, parse_xspace
        xp = find_xplane_pb(rec["dir"])
        if xp is None:
            rec["error"] = "no .xplane.pb written"
            return
        rec["xplane"] = xp
        try:
            planes = parse_xspace(xp)
        except Exception as e:
            rec["error"] = f"xplane parse: {type(e).__name__}: {e}"[:200]
            return
        rec["planes"] = len(planes)
        rec["ok"] = bool(planes)
        if not planes:
            rec["error"] = "xplane parsed but contains no planes"

    # ----------------------------------------------------------- reporting
    def annotation(self) -> List[dict]:
        """Capture records for result-JSON stamping (paths relativized
        to the trace dir would lose the one thing the reader needs, so
        they stay absolute)."""
        return [dict(r) for r in self.captures]
