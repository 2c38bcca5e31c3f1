"""Distributed Queue backed by an actor.

Reference: python/ray/util/queue.py (Queue wrapping a _QueueActor;
blocking put/get with timeouts, Empty/Full mirroring queue module
semantics).
"""

from __future__ import annotations

import os
import time
from typing import Any

import ray_tpu
from ray_tpu.util import tracing

# ``time.monotonic_ns()`` means the same to every process of one host,
# and nothing to another's.
_HOST = os.uname().nodename


class Empty(Exception):
    pass


class Full(Exception):
    pass


class _QueueActor:
    def __init__(self, maxsize: int = 0):
        import collections
        import threading

        self.maxsize = maxsize
        # (item, when it landed here: ``tracing.stamp_ns()``, 0 while no
        # trace sink is live). The stamp never leaves with the item.
        self._items: collections.deque = collections.deque()
        # A queue made with ``waiting_get`` runs two calls at a time: a
        # consumer's ``get_available`` waits HERE for a producer's put.
        self._arrived = threading.Condition()

    def qsize(self) -> int:
        return len(self._items)

    def empty(self) -> bool:
        return not self._items

    def full(self) -> bool:
        return 0 < self.maxsize <= len(self._items)

    def put_nowait(self, item: Any) -> bool:
        with self._arrived:
            if self.full():
                return False
            self._items.append((item, tracing.stamp_ns()))
            self._arrived.notify_all()
            return True

    def put_nowait_batch(self, items: list) -> bool:
        with self._arrived:
            if self.maxsize \
                    and len(self._items) + len(items) > self.maxsize:
                return False
            landed = tracing.stamp_ns()
            self._items.extend((item, landed) for item in items)
            self._arrived.notify_all()
            return True

    def get_nowait(self):
        if not self._items:
            return False, None
        return True, self._items.popleft()[0]

    def get_nowait_batch(self, num_items: int):
        if len(self._items) < num_items:
            return False, None
        return True, [self._items.popleft()[0] for _ in range(num_items)]

    def get_available(self, max_items: int, wait_s: float = 0.0) -> tuple:
        """Whatever is queued, oldest first, at most ``max_items``
        (possibly nothing), after waiting up to ``wait_s`` for the
        first (only a ``waiting_get`` queue is asked to wait: with one
        call at a time the put that would end the wait could not run).
        Replies ``(items, when the oldest landed here or 0, this
        host)``: ``Queue.get_available`` hands its caller the items."""
        with self._arrived:
            if not self._items and wait_s > 0:
                self._arrived.wait(wait_s)
            take = min(max_items, len(self._items))
            taken = [self._items.popleft() for _ in range(take)]
        return ([item for item, _ in taken],
                taken[0][1] if taken else 0, _HOST)


class Queue:
    """Cluster-visible FIFO queue; handles are shareable across tasks
    and actors like any ActorHandle."""

    def __init__(self, maxsize: int = 0, actor_options: dict | None = None,
                 put_timeout_s: float | None = None,
                 waiting_get: bool = False):
        self.maxsize = maxsize
        # ``get_available`` waits inside the actor for a put (the actor
        # runs two calls at a time) instead of asking every 10 ms: a
        # consumer costs a call per delivery, not a hundred a second.
        self.waiting_get = waiting_get
        # What a blocking put waits for room when it is given no timeout
        # of its own (None: for ever, as ``queue.Queue``): a producer
        # whose consumer is gone then raises Full instead of retrying
        # for the life of its thread.
        self.put_timeout_s = put_timeout_s
        # Of the last ``get_available`` that took something: when its
        # oldest item landed in the actor, ``time.monotonic_ns()``; 0 if
        # no trace sink was live there, or the actor is on another host.
        self.oldest_landed_ns = 0
        options = dict(actor_options or {})
        if waiting_get:
            options.setdefault("max_concurrency", 2)
        self.actor = ray_tpu.remote(_QueueActor).options(
            **options).remote(maxsize)

    def __getstate__(self):
        return {"maxsize": self.maxsize, "actor": self.actor,
                "put_timeout_s": self.put_timeout_s,
                "waiting_get": self.waiting_get}

    def __setstate__(self, state):
        self.maxsize = state["maxsize"]
        self.actor = state["actor"]
        self.put_timeout_s = state.get("put_timeout_s")
        self.waiting_get = state.get("waiting_get", False)
        self.oldest_landed_ns = 0

    # -- inspection ---------------------------------------------------
    def qsize(self) -> int:
        return ray_tpu.get(self.actor.qsize.remote())

    def size(self) -> int:
        return self.qsize()

    def empty(self) -> bool:
        return ray_tpu.get(self.actor.empty.remote())

    def full(self) -> bool:
        return ray_tpu.get(self.actor.full.remote())

    # -- put/get ------------------------------------------------------
    def put(self, item: Any, block: bool = True,
            timeout: float | None = None) -> None:
        if not block:
            if not ray_tpu.get(self.actor.put_nowait.remote(item)):
                raise Full
            return
        timeout = self.put_timeout_s if timeout is None else timeout
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            if ray_tpu.get(self.actor.put_nowait.remote(item)):
                return
            if deadline is not None and time.monotonic() >= deadline:
                raise Full
            time.sleep(0.01)

    def put_nowait(self, item: Any) -> None:
        self.put(item, block=False)

    def put_batch(self, items: list, timeout: float | None = None) -> None:
        """Block until ALL of ``items`` are queued, in order: one actor
        call where they fit, a call per ``maxsize`` of them otherwise."""
        items = list(items)
        step = self.maxsize or len(items) or 1
        timeout = self.put_timeout_s if timeout is None else timeout
        deadline = None if timeout is None else time.monotonic() + timeout
        for at in range(0, len(items), step):
            part = items[at:at + step]
            while not ray_tpu.get(self.actor.put_nowait_batch.remote(part)):
                if deadline is not None and time.monotonic() >= deadline:
                    raise Full
                time.sleep(0.01)

    def put_nowait_batch(self, items: list) -> None:
        if not ray_tpu.get(self.actor.put_nowait_batch.remote(
                list(items))):
            raise Full

    def get(self, block: bool = True, timeout: float | None = None) -> Any:
        if not block:
            ok, item = ray_tpu.get(self.actor.get_nowait.remote())
            if not ok:
                raise Empty
            return item
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            ok, item = ray_tpu.get(self.actor.get_nowait.remote())
            if ok:
                return item
            if deadline is not None and time.monotonic() >= deadline:
                raise Empty
            time.sleep(0.01)

    def get_nowait(self) -> Any:
        return self.get(block=False)

    def get_nowait_batch(self, num_items: int) -> list:
        ok, items = ray_tpu.get(
            self.actor.get_nowait_batch.remote(num_items))
        if not ok:
            raise Empty
        return items

    def get_available(self, max_items: int,
                      timeout: float | None = None) -> list:
        """Block until something is queued, then take all of it (at most
        ``max_items``) in ONE actor call: a consumer that has fallen
        behind catches up a call at a time, not an item at a time."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            wait_s = 0.0
            if self.waiting_get:
                wait_s = 0.2 if deadline is None else \
                    max(0.0, min(0.2, deadline - time.monotonic()))
            items, landed_ns, host = ray_tpu.get(
                self.actor.get_available.remote(max_items, wait_s))
            if items:
                self.oldest_landed_ns = landed_ns if host == _HOST else 0
                return items
            if deadline is not None and time.monotonic() >= deadline:
                raise Empty
            if not self.waiting_get:
                time.sleep(0.01)

    def shutdown(self) -> None:
        ray_tpu.kill(self.actor)
