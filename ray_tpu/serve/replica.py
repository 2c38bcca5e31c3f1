"""The replica actor: hosts one copy of the user's deployment callable.

Reference: python/ray/serve/_private/replica.py — ReplicaActor (:233),
handle_request (:391). Each replica tracks its ongoing-request count
(the router's pow-2 signal and the autoscaler's input) and enforces
``max_ongoing_requests`` backpressure.
"""

from __future__ import annotations

import contextlib
import contextvars
import inspect
import threading
import time
from typing import Any

from ray_tpu._private import request_context
from ray_tpu.exceptions import TaskError
from ray_tpu.util import tracing


class BackPressureError(Exception):
    """Replica at max_ongoing_requests (reference: replica raises when
    over capacity so the router retries elsewhere)."""


def stream_request_id(queue) -> "str | None":
    """The id of one streamed request: its per-stream queue's actor id,
    which the caller's handle and the replica both hold, so nothing new
    crosses. Every span of the request's way in and of its tokens' way
    out carries it as ``request``."""
    try:
        return queue.actor._actor_id.hex()[:16]
    except AttributeError:  # not a util.queue.Queue
        return None


# The streamed request this thread is handling: (its id, its open
# ``serve.replica.admit`` phase). The deployment's method reads the id
# for what it submits to (an engine's request carries it) and says when
# that is done, which ends the span.
_STREAM_REQUEST: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_serve_stream_request", default=(None, None))


def current_stream_request_id() -> "str | None":
    """Inside a streaming method of a deployment: the request's id."""
    return _STREAM_REQUEST.get()[0]


def stream_request_admitted() -> None:
    """Inside a streaming method of a deployment: the request has
    reached what serves it (an engine's queue). Ends
    ``serve.replica.admit``; a method that never says so ends it with
    its first chunk."""
    request_id, admit = _STREAM_REQUEST.get()
    if admit is not None:
        _STREAM_REQUEST.set((request_id, None))
        admit.__exit__(None, None, None)


@contextlib.contextmanager
def _stream_request(queue):
    """A streamed request on this thread, from the call's start: its id,
    and ``serve.replica.admit`` open (admission, the call slot taken, the
    engine's queue reached) until ``stream_request_admitted``, the first
    chunk or a failure. ``age_us``: since the caller's ``.remote()`` let
    go of the call."""
    request_id = stream_request_id(queue)
    admit = tracing.phase("serve.replica.admit", cpu=True)
    admit.__enter__()
    if admit.live:
        admit.set(request=request_id, age_us=tracing.age_us(
            request_context.current_submitted_ns()))
    token = _STREAM_REQUEST.set((request_id, admit))
    try:
        yield request_id
    finally:
        stream_request_admitted()
        _STREAM_REQUEST.reset(token)


class Replica:
    """Runs as a ray_tpu actor (one per replica, max_concurrency > 1 so
    requests overlap like the reference's asyncio replicas)."""

    def __init__(self, deployment_name: str, replica_tag: str,
                 deployment_def: Any, init_args: tuple, init_kwargs: dict,
                 user_config: Any = None, max_ongoing_requests: int = 100,
                 handle_args: dict | None = None):
        self._deployment_name = deployment_name
        self._replica_tag = replica_tag
        self._max_ongoing = max_ongoing_requests
        self._lock = threading.Lock()
        self._num_ongoing = 0
        self._num_total = 0
        self._healthy = True

        # Bound sub-deployments arrive as _HandleMarker placeholders and
        # become live DeploymentHandles here inside the replica
        # (reference: deployment_graph_build.py — graph edges become
        # handles).
        def resolve(value):
            from ray_tpu.serve.api import _HandleMarker, get_deployment_handle

            if isinstance(value, _HandleMarker):
                return get_deployment_handle(
                    value.deployment_name, value.app_name)
            return value

        init_args = tuple(resolve(a) for a in init_args)
        init_kwargs = {k: resolve(v) for k, v in init_kwargs.items()}

        if inspect.isclass(deployment_def):
            self._callable = deployment_def(*init_args, **init_kwargs)
        else:
            self._callable = deployment_def
        if user_config is not None:
            self.reconfigure(user_config)

    # ------------------------------------------------------------- data path

    def _admit(self, kwargs: dict):
        """Backpressure admission + multiplex-id extraction; returns
        (kwargs, contextvar token)."""
        from ray_tpu.serve.multiplex import MODEL_ID_KWARG, _request_model_id

        # The router injects the multiplexed model id as a reserved kwarg;
        # it must never reach the user callable. Surface it via the
        # contextvar instead (reference: serve.get_multiplexed_model_id).
        # Thread actors share the caller's kwargs dict object — strip via
        # a copy so a backpressure retry still carries the model id.
        model_id = kwargs.get(MODEL_ID_KWARG)
        if model_id is not None:
            kwargs = {k: v for k, v in kwargs.items()
                      if k != MODEL_ID_KWARG}
        with self._lock:
            if self._num_ongoing >= self._max_ongoing:
                raise BackPressureError(
                    f"{self._replica_tag} at max_ongoing_requests="
                    f"{self._max_ongoing}")
            self._num_ongoing += 1
            self._num_total += 1
        token = (_request_model_id.set(model_id)
                 if model_id is not None else None)
        return kwargs, token

    def _finish(self, token) -> None:
        from ray_tpu.serve.multiplex import _request_model_id

        if token is not None:
            _request_model_id.reset(token)
        with self._lock:
            self._num_ongoing -= 1

    def _invoke(self, method_name: str, args: tuple, kwargs: dict):
        if method_name == "__call__":
            target = self._callable
            if not callable(target):
                raise TypeError(
                    f"Deployment {self._deployment_name} is not callable;"
                    f" specify a method name")
        else:
            target = getattr(self._callable, method_name)
        return target(*args, **kwargs)

    def handle_request(self, method_name: str, args: tuple, kwargs: dict):
        kwargs, token = self._admit(kwargs)
        try:
            result = self._invoke(method_name, args, kwargs)
            if inspect.isgenerator(result):
                # Unary path: a generator result materializes to a
                # chunk list; TRUE incremental delivery is
                # handle.options(stream=True) -> handle_request_streaming.
                result = list(result)
            return result
        finally:
            self._finish(token)

    def handle_request_streaming(self, method_name: str, args: tuple,
                                 kwargs: dict, queue) -> int:
        """True streaming (reference: replica.py:471): chunks flow
        through the shared queue AS the generator yields, so the caller
        consumes while this replica still produces. Protocol:
        ("chunk", value)* then ("end", n) | ("err", exc)."""
        with _stream_request(queue) as request_id:
            return self._stream(method_name, args, kwargs, queue,
                                request_id)

    def _stream(self, method_name: str, args: tuple, kwargs: dict, queue,
                request_id: "str | None") -> int:
        kwargs, token = self._admit(kwargs)
        n = 0
        # A streaming method may have a sibling ``<name>_batches`` that
        # yields LISTS: the chunks that were already waiting when it
        # looked (one, while the consumers keep up). Each list crosses
        # to the queue actor in one call, so a producer that has fallen
        # behind catches up a call at a time, not a chunk at a time; the
        # consumer sees the same chunks in the same order.
        batched = getattr(self._callable, method_name + "_batches", None) \
            if method_name != "__call__" else None
        try:
            result = batched(*args, **kwargs) if batched is not None \
                else self._invoke(method_name, args, kwargs)
            if not inspect.isgenerator(result):
                result = iter([result])
            for chunk in result:
                stream_request_admitted()
                chunks = chunk if batched is not None else (chunk,)
                try:
                    # The entry point's cost per chunk, and through
                    # the queue actor the core runtime's.
                    with tracing.phase("serve.stream.put") as hop:
                        if hop.live:
                            hop.set(request=request_id, tokens=len(chunks))
                        if len(chunks) == 1:
                            queue.put(("chunk", chunks[0]))
                        else:
                            queue.put_batch([("chunk", c) for c in chunks])
                except Exception:  # noqa: BLE001 — consumer abandoned
                    # The caller tore down the queue (early break), or
                    # has taken nothing for the queue's put_timeout_s:
                    # stop producing — cancellation, not an error.
                    getattr(result, "close", lambda: None)()
                    return n
                n += len(chunks)
            queue.put(("end", n))
            return n
        except BaseException as exc:  # noqa: BLE001 — shipped to caller
            try:
                queue.put(("err", exc))
            except Exception:  # noqa: BLE001 — queue already gone
                pass
            raise
        finally:
            self._finish(token)

    # ---------------------------------------------------------- control path

    def reconfigure(self, user_config: Any) -> None:
        hook = getattr(self._callable, "reconfigure", None)
        if hook is not None:
            hook(user_config)

    def check_health(self) -> bool:
        hook = getattr(self._callable, "check_health", None)
        if hook is not None:
            hook()
        return True

    def get_metrics(self) -> dict:
        with self._lock:
            metrics = {
                "replica_tag": self._replica_tag,
                "num_ongoing_requests": self._num_ongoing,
                "num_total_requests": self._num_total,
                "timestamp": time.time(),
            }
        # User-callable load gauges (the LLM engine's engine_depth):
        # merged in for the controller's autoscale pass — a deployment
        # whose queue lives INSIDE the callable reports it here.
        hook = getattr(self._callable, "serve_metrics", None)
        if hook is not None:
            try:
                extra = hook()
                if isinstance(extra, dict):
                    metrics.update(extra)
            except Exception:  # noqa: BLE001 — metrics must not fail probes
                pass
        return metrics

    def prepare_for_shutdown(self) -> None:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with self._lock:
                if self._num_ongoing == 0:
                    break
            time.sleep(0.02)
        # Stop this instance's @serve.batch batcher threads: queued
        # callers fail typed instead of hanging, and no batcher thread
        # outlives the deployment.
        from ray_tpu.serve.batching import shutdown_batchers

        try:
            shutdown_batchers(self._callable)
        except Exception:  # noqa: BLE001 — teardown is best-effort
            pass
        hook = getattr(self._callable, "__del__", None)
        if hook is not None:
            try:
                hook()  # e.g. LLMEngineServer.__del__ stops its engine thread
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
