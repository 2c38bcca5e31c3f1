"""Actor execution runtime.

TPU-native analogue of the reference's actor machinery: per-actor ordered
submission queues (reference:
src/ray/core_worker/transport/sequential_actor_submit_queue.h vs
out_of_order_actor_submit_queue.h), server-side actor scheduling queue with
concurrency groups (transport/actor_scheduling_queue.h,
concurrency_group_manager.h), async actors on an event loop
(transport/fiber.h), and GCS-driven restart (gcs_actor_manager.h).

Each actor runs on a dedicated thread (max_concurrency=1 ⇒ strictly
ordered calls) or a small thread pool / asyncio loop for concurrent and
async actors. Actor resources are leased for the actor's lifetime.
"""

from __future__ import annotations

import asyncio
import inspect
import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable

from ray_tpu._private.ids import ActorID, ObjectID
from ray_tpu._private.scheduler import format_traceback
from ray_tpu.exceptions import (
    ActorDiedError,
    ActorError,
    PendingCallsLimitExceeded,
    TaskCancelledError,
)
from ray_tpu.util import tracing


class _ExitActor(BaseException):
    """Raised by exit_actor() to unwind out of the running method."""


@dataclass
class _ActorCall:
    method_name: str
    args: tuple
    kwargs: dict
    return_ids: list[ObjectID]
    cancelled: bool = False
    # Absolute end-to-end deadline (time.time()); checked before the
    # method runs so a call whose budget died queued behind earlier
    # calls seals TaskTimeoutError instead of executing.
    deadline: "float | None" = None
    # While a sink is live where the call is submitted
    # (``tracing.live()``): when its caller let go of it,
    # ``time.monotonic_ns()``, and the caller's span context. The
    # executors that ship a call to another process ship neither.
    submitted_ns: int = 0
    trace_ctx: "tuple | None" = None


def _call_deadline_error(call: _ActorCall, cls_name: str):
    """TaskTimeoutError for an actor call whose budget died queued
    (None while the deadline is still live) — shared by every actor
    executor (LocalActor / ProcessActor / RemoteActor)."""
    if call.deadline is None or time.time() <= call.deadline:
        return None
    from ray_tpu.exceptions import TaskTimeoutError

    return TaskTimeoutError(f"{cls_name}.{call.method_name}",
                            "actor_queue", call.deadline)


class LocalActor:
    """A live actor instance bound to an executor thread/loop."""

    def __init__(
        self,
        actor_id: ActorID,
        cls: type,
        init_args: tuple,
        init_kwargs: dict,
        runtime,
        *,
        max_concurrency: int = 1,
        max_restarts: int = 0,
        max_pending_calls: int = -1,
        creation_return_id: ObjectID | None = None,
        on_death: Callable[[ActorID, str], None] | None = None,
        on_restart: Callable[[ActorID], None] | None = None,
    ):
        self.actor_id = actor_id
        self._cls = cls
        self._init_args = init_args
        self._init_kwargs = init_kwargs
        self._runtime = runtime
        self._max_concurrency = max(1, max_concurrency)
        self._max_restarts = max_restarts
        self._max_pending_calls = max_pending_calls
        self._on_death = on_death
        self._on_restart = on_restart
        self._num_restarts = 0
        self._queue: queue.Queue[_ActorCall | None] = queue.Queue()
        self._pending = 0
        self._lock = threading.Lock()
        self._dead = False
        self._death_reason: str | None = None
        self._instance = None
        self._is_async = _has_async_methods(cls)
        self._creation_return_id = creation_return_id
        self._started = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"ray_tpu-actor-{cls.__name__}", daemon=True)
        self._thread.start()

    # ----------------------------------------------------------------- calls

    def submit(self, call: _ActorCall) -> None:
        with self._lock:
            if self._dead:
                self._fail_call(call, ActorDiedError(
                    self.actor_id, self._death_reason or "actor has died"))
                return
            if 0 <= self._max_pending_calls <= self._pending:
                self._fail_call(call, PendingCallsLimitExceeded(
                    f"actor {self._cls.__name__} has {self._pending} pending calls"))
                return
            self._pending += 1
            # put() happens under the lock so _mark_dead's drain (same lock)
            # can never miss an in-flight call.
            self._queue.put(call)

    def _fail_call(self, call: _ActorCall, error: BaseException) -> None:
        for rid in call.return_ids:
            self._runtime.store.put_error(rid, error)

    # ------------------------------------------------------------- execution

    def _run(self) -> None:
        try:
            self._instance = self._cls(*self._init_args, **self._init_kwargs)
        except BaseException as exc:  # noqa: BLE001 — constructor failure kills actor
            self._mark_dead(f"constructor failed: {exc!r}")
            if self._creation_return_id is not None:
                self._runtime.store.put_error(
                    self._creation_return_id,
                    ActorError(exc, format_traceback(exc),
                               f"{self._cls.__name__}.__init__"))
            return
        if self._creation_return_id is not None:
            self._runtime.store.put(self._creation_return_id, None)
        self._started.set()
        if self._is_async:
            self._run_async_loop()
        elif self._max_concurrency > 1:
            self._run_threadpool()
        else:
            self._run_sequential()

    def _run_sequential(self) -> None:
        while True:
            call = self._queue.get()
            if call is None:
                return
            self._execute(call)
            # Unbind before re-blocking: a stale frame local would keep
            # the last call's args (and any nested ObjectRefs) alive.
            call = None

    def _run_threadpool(self) -> None:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=self._max_concurrency) as pool:
            while True:
                call = self._queue.get()
                if call is None:
                    return
                pool.submit(self._execute, call)
                call = None  # don't retain across the blocking get

    def _run_async_loop(self) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        sem = asyncio.Semaphore(self._max_concurrency)

        async def drive():
            while True:
                call = await loop.run_in_executor(None, self._queue.get)
                if call is None:
                    return
                await sem.acquire()

                async def run_one(c=call):
                    try:
                        await loop.run_in_executor(None, lambda: None)  # yield
                        await self._execute_async(c)
                    finally:
                        sem.release()

                loop.create_task(run_one())

        try:
            loop.run_until_complete(drive())
        finally:
            pending = asyncio.all_tasks(loop)
            for task in pending:
                task.cancel()
            loop.run_until_complete(asyncio.gather(*pending, return_exceptions=True))
            loop.close()

    def _execute(self, call: _ActorCall) -> None:
        with self._lock:
            self._pending -= 1
        if call.cancelled:
            self._fail_call(call, TaskCancelledError())
            return
        expired = _call_deadline_error(call, self._cls.__name__)
        if expired is not None:
            self._fail_call(call, expired)
            return
        from ray_tpu._private import request_context

        ctx_token = request_context.set_call(call.deadline,
                                             call.submitted_ns)
        span_token = tracing.attach(call.trace_ctx)
        try:
            with tracing.phase("runtime.actor.run") as hop:
                self._describe(hop, call)
                method = getattr(self._instance, call.method_name)
                result = method(*call.args, **call.kwargs)
                self._store_result(call, result)
        except _ExitActor:
            self._store_result(call, None)
            self.kill("exit_actor() was called", no_restart=True)
        except BaseException as exc:  # noqa: BLE001 — reported on the ref
            self._fail_call(call, ActorError(
                exc, format_traceback(exc),
                f"{self._cls.__name__}.{call.method_name}"))
        finally:
            tracing.detach(span_token)
            request_context.reset_call(ctx_token)

    async def _execute_async(self, call: _ActorCall) -> None:
        with self._lock:
            self._pending -= 1
        if call.cancelled:
            self._fail_call(call, TaskCancelledError())
            return
        expired = _call_deadline_error(call, self._cls.__name__)
        if expired is not None:
            self._fail_call(call, expired)
            return
        from ray_tpu._private import request_context

        ctx_token = request_context.set_call(call.deadline,
                                             call.submitted_ns)
        span_token = tracing.attach(call.trace_ctx)
        try:
            # The span covers the awaits too.
            with tracing.phase("runtime.actor.run") as hop:
                self._describe(hop, call)
                method = getattr(self._instance, call.method_name)
                result = method(*call.args, **call.kwargs)
                if inspect.isawaitable(result):
                    result = await result
                self._store_result(call, result)
        except _ExitActor:
            self._store_result(call, None)
            self.kill("exit_actor() was called", no_restart=True)
        except BaseException as exc:  # noqa: BLE001
            self._fail_call(call, ActorError(
                exc, format_traceback(exc),
                f"{self._cls.__name__}.{call.method_name}"))
        finally:
            tracing.detach(span_token)
            request_context.reset_call(ctx_token)

    def _describe(self, hop, call: _ActorCall) -> None:
        """``runtime.actor.run``'s attributes, taken while a sink is
        live: ``age_us`` is submit -> start, through the per-actor
        submit-queue thread and this actor's mailbox."""
        if hop.live:
            hop.set(actor=self.actor_id.hex()[:8],
                    method=f"{self._cls.__name__}.{call.method_name}",
                    age_us=tracing.age_us(call.submitted_ns))

    def _store_result(self, call: _ActorCall, result: Any) -> None:
        store = self._runtime.store
        if len(call.return_ids) == 1:
            store.put(call.return_ids[0], result)
        elif len(call.return_ids) > 1:
            values = list(result) if result is not None else [None] * len(call.return_ids)
            for rid, value in zip(call.return_ids, values):
                store.put(rid, value)

    # ----------------------------------------------------------------- death

    def kill(self, reason: str = "killed via kill()", no_restart: bool = True) -> None:
        restartable = (not no_restart) and self._num_restarts < self._max_restarts
        # A restarting actor keeps its resource lease and GCS liveness, so
        # on_death (which releases the lease) only fires on permanent death.
        self._mark_dead(reason, notify=not restartable)
        self._queue.put(None)  # unblock executor loop
        if restartable:
            self._restart()

    def _mark_dead(self, reason: str, notify: bool = True) -> None:
        with self._lock:
            if self._dead:
                return
            self._dead = True
            self._death_reason = reason
            # Fail everything still queued.
            drained: list[_ActorCall] = []
            try:
                while True:
                    item = self._queue.get_nowait()
                    if item is not None:
                        drained.append(item)
            except queue.Empty:
                pass
            self._pending = 0
        for call in drained:
            self._fail_call(call, ActorDiedError(self.actor_id, reason))
        if notify and self._on_death is not None:
            self._on_death(self.actor_id, reason)

    def _restart(self) -> None:
        """Recreate the instance (reference: GcsActorManager restart path)."""
        with self._lock:
            self._num_restarts += 1
            self._dead = False
            self._death_reason = None
        self._instance = None
        self._started.clear()
        self._creation_return_id = None
        self._thread = threading.Thread(
            target=self._run, name=f"ray_tpu-actor-{self._cls.__name__}-r{self._num_restarts}",
            daemon=True)
        self._thread.start()
        if self._on_restart is not None:
            self._on_restart(self.actor_id)

    def is_dead(self) -> bool:
        with self._lock:
            return self._dead

    def wait_started(self, timeout: float | None = None) -> bool:
        return self._started.wait(timeout)


def _has_async_methods(cls: type) -> bool:
    return any(
        inspect.iscoroutinefunction(m)
        for _, m in inspect.getmembers(cls, predicate=inspect.isfunction)
    )


def exit_actor():
    """Terminate the current actor from inside a method.

    Reference: ray.actor.exit_actor (python/ray/actor.py).
    """
    raise _ExitActor()
