"""Per-call request context: the in-flight call's end-to-end deadline.

The PR-7 deadline machinery stamps an ABSOLUTE deadline onto every
task/actor call and checks it at each pipeline stage — but until now
the budget was invisible to the USER CODE the call finally runs. A
serve replica hosting a long-lived engine (the LLM engine's internal
waiting queue and decode loop) needs the remaining budget so ITS
stages can refuse dead work too, instead of decoding tokens nobody is
waiting for.

The actor runtimes set the contextvar around each method invocation;
``ray_tpu.runtime_context.get_runtime_context().get_task_deadline()``
reads it from inside the method (None = no deadline armed).
Contextvars propagate into coroutines and stay isolated per thread, so
concurrent actor calls never see each other's budgets.
"""

from __future__ import annotations

import contextvars

_DEADLINE: "contextvars.ContextVar[float | None]" = contextvars.ContextVar(
    "ray_tpu_call_deadline", default=None)


# When the in-flight call's caller let go of it (``time.monotonic_ns()``
# there; 0: not stamped, no trace sink was live): what a span the method
# opens at its entry reports as its ``age_us``.
_SUBMITTED_NS: "contextvars.ContextVar[int]" = contextvars.ContextVar(
    "ray_tpu_call_submitted_ns", default=0)


def set_call(deadline: "float | None", submitted_ns: int = 0):
    """Install the current call's absolute deadline (time.time()) and,
    for a call that was stamped, its submission stamp; returns the
    token for :func:`reset_call`."""
    return (_DEADLINE.set(deadline),
            _SUBMITTED_NS.set(submitted_ns) if submitted_ns else None)


def reset_call(token) -> None:
    deadline, submitted = token
    _DEADLINE.reset(deadline)
    if submitted is not None:
        _SUBMITTED_NS.reset(submitted)


def current_submitted_ns() -> int:
    """The in-flight call's submission stamp, or 0."""
    return _SUBMITTED_NS.get()


def current_deadline() -> "float | None":
    """The in-flight call's absolute deadline, or None."""
    return _DEADLINE.get()
