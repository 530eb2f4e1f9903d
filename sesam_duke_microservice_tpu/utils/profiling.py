"""Device-timeline tracing hooks (the TPU equivalent of the reference's
``PROFILE=1`` -> ``Processor.setPerformanceProfiling`` per-phase timing,
App.java:239-244,345,466 — SURVEY.md section 5.1).

Three levels:

  * ``PROFILE=1`` — per-batch wall-clock logs + ProfileStats counters
    (engine.processor / engine.device_matcher), mirroring the reference's
    listener-level logging (IncrementalRecordLinkageMatchListener.java:42-52).
  * ``PROFILE_TRACE_DIR=/path`` — additionally capture ``jax.profiler``
    traces (XLA op timeline, HBM usage, fusion view in TensorBoard /
    xprof) for the first ``PROFILE_TRACE_BATCHES`` (default 3) scoring
    batches.  Bounded by default: traces are large and the service is
    long-running.  The spent budget is resettable at runtime
    (``reset_trace_budget`` / ``POST /debug/profile/reset``) so a
    long-running service can re-capture after a config reload.
  * **on-demand capture** (ISSUE 2) — ``start_capture(seconds)`` /
    ``POST /debug/profile?seconds=N`` opens a ``jax.profiler`` trace NOW
    for N seconds, no restart and no env preconfiguration.  While a
    capture is live, tracing spans created with ``annotate=True`` (every
    host step of a served microbatch) also enter
    ``jax.profiler.TraceAnnotation`` as ``duke/<span name>``, and the
    capture opens with a ``duke/clock.anchor`` that places the program's
    monotonic clock on the trace's.
"""

from __future__ import annotations

import contextlib
import logging
import tempfile
import threading
import time
from typing import Any, Dict, Optional

from ..telemetry import tracing as _tracing
from ..telemetry.env import env_int, env_str

logger = logging.getLogger("profiling")

_lock = threading.Lock()
_traced_batches = 0


def trace_dir() -> str:
    return env_str("PROFILE_TRACE_DIR", "")


def _trace_budget() -> int:
    return env_int("PROFILE_TRACE_BATCHES", 3)


def reset_trace_budget() -> int:
    """Re-arm the PROFILE_TRACE_DIR batch-capture budget (the spent count
    used to be process-lifetime-once).  Returns the re-armed budget."""
    global _traced_batches
    with _lock:
        _traced_batches = 0
    return _trace_budget()


@contextlib.contextmanager
def trace_batch(label: str):
    """Wrap one scoring batch in a jax.profiler trace when enabled.

    No-op unless ``PROFILE_TRACE_DIR`` is set and the trace budget has not
    been spent.  Only profiler setup/teardown failures are swallowed (they
    log) — exceptions from the traced block itself propagate untouched, and
    tracing must never take down a batch.
    """
    global _traced_batches
    directory = trace_dir()
    if not directory:
        yield
        return
    with _lock:
        if _traced_batches >= _trace_budget():
            yield
            return
        _traced_batches += 1
        n = _traced_batches
    stack = contextlib.ExitStack()
    entered = False
    try:
        import jax

        stack.enter_context(jax.profiler.trace(directory))
        stack.enter_context(jax.profiler.TraceAnnotation(label))
        entered = True
    except Exception:
        # return the unused budget slot so later healthy batches still trace
        with _lock:
            _traced_batches -= 1
        logger.exception("device trace setup failed (batch continues)")
    try:
        yield
    finally:
        try:
            stack.close()
            if entered:
                logger.info("captured device trace %d/%d (%s) into %s",
                            n, _trace_budget(), label, directory)
        except Exception:
            logger.exception(
                "device trace teardown failed (batch continues)"
            )


# -- on-demand capture (POST /debug/profile) ---------------------------------

# seam for tests: the two jax.profiler touch points, monkeypatchable so
# endpoint smoke tests never spin a real profiler session
def profiler_start(logdir: str) -> None:
    import jax

    jax.profiler.start_trace(logdir)


def profiler_stop() -> None:
    import jax

    jax.profiler.stop_trace()


MAX_CAPTURE_SECONDS = 600.0

_capture_lock = threading.Lock()
_capture: Optional[Dict[str, Any]] = None


def capture_status() -> Optional[Dict[str, Any]]:
    """The live capture's public info, or None."""
    with _capture_lock:
        if _capture is None:
            return None
        info = {k: _capture[k] for k in
                ("dir", "seconds", "started_unix", "owner",
                 "deadline_unix")}
        info["remaining_seconds"] = round(
            max(0.0, _capture["until"] - time.monotonic()), 3)
        return info


def start_capture(seconds: float, logdir: Optional[str] = None,
                  owner: str = "app") -> Dict[str, Any]:
    """Open a ``jax.profiler`` capture NOW for ``seconds`` seconds.

    Generalizes the first-N-batches ``PROFILE_TRACE_DIR`` capture to any
    moment in a running service: a timer thread stops the capture, and
    while it is live the request-tracing layer bridges its engine phase
    spans into device TraceAnnotations.  The profiler is one per
    process but the serving planes (app / replica / federation) each
    expose the endpoint, so a second ``start_capture`` — from ANY plane
    — raises ``CaptureActiveError`` carrying the live capture's owner
    plane and deadline for the endpoint's 409 body; failures to start
    propagate to the caller (the endpoint answers 500) with no state
    latched.  ``owner`` names the requesting plane.
    """
    seconds = float(seconds)
    if not (0 < seconds <= MAX_CAPTURE_SECONDS):
        raise ValueError(
            f"capture seconds must be in (0, {MAX_CAPTURE_SECONDS:g}]"
        )
    global _capture
    with _capture_lock:
        if _capture is not None:
            raise CaptureActiveError(
                f"a device capture (owner={_capture['owner']}) is "
                f"already running into {_capture['dir']}",
                owner=_capture["owner"],
                deadline_unix=_capture["deadline_unix"],
                remaining_seconds=round(
                    max(0.0, _capture["until"] - time.monotonic()), 3),
            )
        directory = (logdir or trace_dir()
                     or tempfile.mkdtemp(prefix="duke-profile-"))
        profiler_start(directory)
        _tracing.set_device_annotations(True)
        # the capture's duke/clock.anchor: a program monotonic time t
        # sits at the anchor event's start + (t - anchor_monotonic_ns)
        anchor_ns = _tracing.clock_anchor()
        timer = threading.Timer(seconds, stop_capture)
        timer.daemon = True
        _capture = {
            "dir": directory,
            "seconds": seconds,
            "started_unix": round(time.time(), 3),
            "deadline_unix": round(time.time() + seconds, 3),
            "until": time.monotonic() + seconds,
            "owner": owner,
            "anchor_monotonic_ns": anchor_ns,
            "timer": timer,
        }
        timer.start()
        logger.info("on-demand device capture started: %.3gs into %s "
                    "(owner=%s)", seconds, directory, owner)
        return {k: _capture[k] for k in
                ("dir", "seconds", "started_unix", "deadline_unix",
                 "owner", "anchor_monotonic_ns")}


def stop_capture() -> Optional[Dict[str, Any]]:
    """End the live capture (timer callback; also callable early).
    Returns the finished capture's info, or None if none was live."""
    global _capture
    with _capture_lock:
        if _capture is None:
            return None
        done, _capture = _capture, None
        done.pop("until", None)
        timer = done.pop("timer", None)
        if timer is not None:
            timer.cancel()
        _tracing.set_device_annotations(False)
        try:
            profiler_stop()
            logger.info("on-demand device capture finished: %s",
                        done["dir"])
        except Exception:
            logger.exception("on-demand capture teardown failed")
            done["error"] = "profiler stop failed (see logs)"
        return done


class CaptureActiveError(RuntimeError):
    """A second ``start_capture`` while one is live (endpoint: 409).

    Carries the live capture's owner plane and deadline so the 409 body
    can say WHO holds the profiler and until when — a capture started
    through one plane must never swallow another plane's request with a
    misleading success."""

    def __init__(self, message: str, owner: Optional[str] = None,
                 deadline_unix: Optional[float] = None,
                 remaining_seconds: Optional[float] = None):
        super().__init__(message)
        self.owner = owner
        self.deadline_unix = deadline_unix
        self.remaining_seconds = remaining_seconds
