"""Dual-sink trace annotations for the checkpoint pipeline.

Reference parity: the reference emits progress/throughput lines
(scheduler.py:96-175) but no timeline tracing. Here every annotation
lands in TWO places at once:

- the **flight recorder** (telemetry/trace.py) — always on, bounded
  ring, exported per-operation as Chrome trace JSON; this is what the
  stall watchdog and ``python -m torchsnapshot_tpu.telemetry trace``
  consume, profiler session or not;
- the **jax profiler timeline** — when a session is active
  (``jax.profiler.start_trace`` or the TensorBoard plugin), the same
  span appears on the XPlane timeline next to device compute, making
  D2H/compute/I-O overlap directly visible. With no session active the
  TraceAnnotation is a couple of cheap TraceMe calls; without jax
  importable it degrades away entirely.

jax availability is resolved once at import time — these annotations
sit on the per-buffer hot path. Span names are declared once in
``telemetry/names.py`` (``tools/check_span_names.py`` lints call
sites); keyword args become the recorder span's args (the jax side
carries the name only). For the names ``telemetry/names.py`` lists
(``SPANS_WITH_THREAD_USAGE``, ``SPANS_WITH_PROCESS_USAGE``) the span
also ends with the kernel's account between its two ends
(``names.USAGE_ARGS``: CPU microseconds, user and system, and, where
the kernel counts them, bytes faulted in), from one ``getrusage`` each
side on the span's own thread; any other name pays one dictionary
lookup.

NOTE: the jax annotation is thread-local begin/end, so call sites that
hold a span across an ``await`` should use the recorder directly
(``telemetry.trace.get_recorder().span(...)``, which tracks per
asyncio task) rather than this helper — an interleaved task on the
same thread would otherwise mis-nest the XPlane timeline.

The recorder stamps every span with the op id and parent span of the
caller's ``contextvars`` context. Tasks inherit that context; executor
threads do not, so hops on the hot path go through
:func:`run_in_executor`.
"""

from __future__ import annotations

import asyncio
import contextvars
import resource
from concurrent.futures import Executor
from typing import Any, Callable, Dict, Optional

from ..telemetry import names
from ..telemetry.trace import get_recorder

try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover - jax always present in this repo
    _TraceAnnotation = None


# Whose account a sampled span reads, by the attribute of ``resource``
# that names it (looked up per sample: absent on a platform without it,
# and the span then carries no usage).
_USAGE_WHO: Dict[str, str] = {
    **{name: "RUSAGE_THREAD" for name in names.SPANS_WITH_THREAD_USAGE},
    **{name: "RUSAGE_SELF" for name in names.SPANS_WITH_PROCESS_USAGE},
}


def _usage(who: str) -> Optional["resource.struct_rusage"]:
    target = getattr(resource, who, None)
    return None if target is None else resource.getrusage(target)


def _usage_between(
    before: "resource.struct_rusage", after: "resource.struct_rusage"
) -> Dict[str, int]:
    user, system, faults = names.USAGE_ARGS
    spent = {
        user: round((after.ru_utime - before.ru_utime) * 1e6),
        system: round((after.ru_stime - before.ru_stime) * 1e6),
    }
    # A thread or a process that has never faulted is a kernel that
    # keeps no count (gVisor reports 0 throughout): absent, not zero.
    if after.ru_minflt:
        spent[faults] = (
            after.ru_minflt - before.ru_minflt
        ) * resource.getpagesize()
    return spent


class _DualAnnotation:
    """Flight-recorder span + jax TraceAnnotation, one context manager
    (hand-rolled: this wraps every buffer's staging/write/read, and a
    generator-based contextmanager costs ~3x per entry). ``op`` not
    None makes the span an operation's envelope
    (``SpanRecorder.begin_op``). A span whose name
    ``telemetry/names.py`` lists for it also carries the kernel's
    account of its thread, or of the process, between its two ends
    (``names.USAGE_ARGS``): a begin and an end are on one thread here."""

    __slots__ = (
        "_name", "_args", "_op", "_token", "_jax", "_late", "_who", "_before",
    )

    def __init__(self, name: str, args: dict, op: Optional[int] = None) -> None:
        self._name = name
        self._args = args
        self._op = op
        self._token = 0
        self._jax = None
        self._late: Optional[dict] = None
        self._who = _USAGE_WHO.get(name)
        self._before = None

    def annotate(self, **args: Any) -> None:
        """Args known only once the work is done (a pickle's size); they
        join the recorder span when it ends."""
        self._late = {**(self._late or {}), **args}

    def __enter__(self) -> "_DualAnnotation":
        if self._op is None:
            self._token = get_recorder().begin(self._name, **self._args)
        else:
            self._token = get_recorder().begin_op(
                self._name, self._op, **self._args
            )
        if _TraceAnnotation is not None:
            self._jax = _TraceAnnotation(self._name)
            self._jax.__enter__()
        if self._who is not None:
            # Last in, first out: the span's own bookkeeping is outside.
            self._before = _usage(self._who)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # Idempotent: :func:`end` may run again from a ``finally``.
        before, self._before = self._before, None
        if before is not None:
            self.annotate(**_usage_between(before, _usage(self._who)))
        jax_side, self._jax = self._jax, None
        try:
            if jax_side is not None:
                jax_side.__exit__(exc_type, exc, tb)
        finally:
            get_recorder().end(self._token, **(self._late or {}))


def trace_annotation(name: str, **args: Any) -> "_DualAnnotation":
    """A context manager placing ``name`` on the flight recorder AND
    the active jax profiler timeline (thread-local on the jax side —
    safe on executor threads; see module note for coroutines)."""
    return _DualAnnotation(name, args)


def op_annotation(name: str, op: int = 0, **args: Any) -> "_DualAnnotation":
    """:func:`trace_annotation` for an operation's envelope span: ``op``
    0 opens a new operation, another value joins that one. For
    envelopes that open and close on one thread."""
    return _DualAnnotation(name, args, op)


def begin(name: str, op: int = 0, **args: Any) -> _DualAnnotation:
    """:func:`op_annotation`, opened: for an envelope that has to close
    before the end of its ``try`` block (the report is emitted after
    it). Pair with :func:`end`, once there and once in the ``finally``."""
    annotation = _DualAnnotation(name, args, op)
    annotation.__enter__()
    return annotation


def end(annotation: _DualAnnotation) -> None:
    """Close an envelope opened by :func:`begin`; a no-op the second time."""
    annotation.__exit__(None, None, None)


def run_in_executor(
    executor: Optional[Executor], fn: Callable[..., Any], *args: Any
) -> "asyncio.Future[Any]":
    """``loop.run_in_executor`` that carries the caller's context (the
    op id and parent span the recorder stamps) onto the worker thread."""
    return asyncio.get_running_loop().run_in_executor(
        executor, contextvars.copy_context().run, fn, *args
    )
