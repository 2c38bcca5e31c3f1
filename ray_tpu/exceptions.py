"""User-visible exceptions.

Mirrors the reference's exception taxonomy (reference:
python/ray/exceptions.py): task errors wrap the user exception with the
remote traceback, actor errors/actor-death, object loss, and timeouts.
"""

from __future__ import annotations


class RayTpuError(Exception):
    """Base class for all framework errors."""


class TaskError(RayTpuError):
    """A remote task raised an exception.

    The original exception is available as ``.cause``; re-raising through
    ``get()`` chains the remote traceback text so users see where the
    failure happened (reference: python/ray/exceptions.py RayTaskError).
    """

    def __init__(self, cause: BaseException, remote_traceback: str = "",
                 task_name: str = ""):
        self.cause = cause
        self.remote_traceback = remote_traceback
        self.task_name = task_name
        super().__init__(str(cause))

    def __str__(self):
        base = f"Task '{self.task_name}' failed: {type(self.cause).__name__}: {self.cause}"
        if self.remote_traceback:
            base += "\n\nRemote traceback:\n" + self.remote_traceback
        return base


class ActorError(TaskError):
    """An actor method raised an exception."""


class ActorDiedError(RayTpuError):
    """The actor was dead when a method call was attempted."""

    def __init__(self, actor_id=None, reason: str = "actor has died"):
        self.actor_id = actor_id
        self.reason = reason
        super().__init__(reason)


class ActorUnavailableError(RayTpuError):
    """The actor is temporarily unreachable (e.g. restarting)."""


class ObjectLostError(RayTpuError):
    """An object could not be found in any store and had no lineage."""

    def __init__(self, object_ref=None, reason: str = "object lost"):
        self.object_ref = object_ref
        super().__init__(reason)


class ObjectFreedError(ObjectLostError):
    """The object was explicitly freed."""


class GetTimeoutError(RayTpuError, TimeoutError):
    """``get()`` did not complete within the requested timeout."""


class TaskTimeoutError(TaskError):
    """The task's end-to-end deadline expired before it produced a
    result. Sealed onto the task's return refs by whichever pipeline
    stage found the budget dead (``.stage``: submit / queued / dispatch
    / admitted / worker / execute / actor_queue), so ``get()`` raises
    it instead of executing dead work. NOT retryable by the runtime —
    the deadline belongs to the caller; resubmit with a fresh budget.
    """

    def __init__(self, task_name: str = "", stage: str = "",
                 deadline: float = 0.0):
        self.stage = stage
        self.deadline = deadline
        cause = TimeoutError(
            f"end-to-end deadline expired at stage {stage!r}")
        super().__init__(cause, "", task_name)

    def __reduce__(self):
        # TaskError's base reduce re-calls __init__ with the formatted
        # message; this subclass takes different args and must round-
        # trip through store seals and RPC error blobs.
        return (TaskTimeoutError,
                (self.task_name, self.stage, self.deadline))


class SystemOverloadedError(RayTpuError):
    """Admission control rejected the work instead of queueing it
    unboundedly (queue-depth cap, memory watermark, or a serve tier at
    ``max_queued_requests``). RETRYABLE: nothing executed — back off
    and resubmit (the HTTP tier maps this to a 503)."""

    def __init__(self, reason: str = "system overloaded",
                 retry_after_s: float = 0.1):
        self.retry_after_s = retry_after_s
        super().__init__(reason)

    def __reduce__(self):
        return (SystemOverloadedError,
                (self.args[0] if self.args else "system overloaded",
                 self.retry_after_s))


class CacheExhaustedError(SystemOverloadedError):
    """The LLM engine's paged KV-cache (or its bounded waiting queue)
    cannot hold this request right now. Subclasses
    ``SystemOverloadedError`` so it sheds through the existing typed
    overload path (serve handle callers see it typed; the HTTP tier
    maps it to 503 + Retry-After). RETRYABLE: nothing decoded — back
    off and resubmit."""

    def __init__(self, reason: str = "KV cache exhausted",
                 retry_after_s: float = 0.5):
        super().__init__(reason, retry_after_s)

    def __reduce__(self):
        return (CacheExhaustedError,
                (self.args[0] if self.args else "KV cache exhausted",
                 self.retry_after_s))


class ChipOwnershipError(RayTpuError):
    """TPU work was asked of a process that cannot have the chip: a chip
    belongs to one process at a time, and it is already held by the host
    process or leased to another child. Never answered by running the
    work on a CPU instead."""


class TaskCancelledError(RayTpuError):
    """The task was cancelled before or during execution."""

    def __init__(self, task_id=None):
        self.task_id = task_id
        super().__init__("task was cancelled")


class PendingCallsLimitExceeded(RayTpuError):
    """Actor's pending call queue exceeded max_pending_calls."""


class WorkerCrashedError(RayTpuError):
    """A worker process died while executing a task (system failure —
    retried when retries remain, reference: python/ray/exceptions.py
    WorkerCrashedError)."""


class RuntimeEnvSetupError(RayTpuError):
    """Failed to set up the runtime environment for a task/actor."""


class OutOfMemoryError(RayTpuError):
    """The object store or worker heap exceeded its memory budget."""


class PlacementGroupError(RayTpuError):
    """Placement group creation/scheduling failed."""
