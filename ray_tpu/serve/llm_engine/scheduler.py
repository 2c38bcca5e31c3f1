"""Prefill/decode scheduler: admission, interleave, preemption,
deadlines.

Request lifecycle (states are the ``TaskTimeoutError.stage`` contract:
a budget dying in the bounded queue seals stage ``llm_queue``, one
dying during prefill/decode seals ``llm_decode``)::

    submit -> WAITING -> PREFILL -> DECODE -> finished
                 ^          |          |
                 +----------+----------+   (preemption: blocks freed,
                        recompute-on-resume re-prefills prompt +
                        generated-so-far, generation continues from
                        the exact token it stopped at)

Policy decisions (the continuous-batching loop consults these; jax
work stays in engine.py):

- **admission** from a BOUNDED waiting queue (``llm_max_waiting``;
  full ⇒ typed :class:`CacheExhaustedError` shed at submit) — at most
  one request prefills at a time, claimed whenever a decode row is
  free;
- **chunked prefill interleave**: each engine iteration runs at most
  ONE prefill chunk, then a decode step for every active stream — a
  10k-token prompt costs in-flight streams one chunk of extra latency
  per step, never a stall;
- **preemption on cache pressure**: when the block pool runs dry the
  LOWEST-PROGRESS decode request (fewest generated tokens — the
  cheapest recompute, ties toward the latest admit) releases its
  blocks and re-queues at the FRONT of the waiting queue. On resume it
  re-prefills ``prompt + output[:-1]`` and continues from
  ``output[-1]`` — with greedy sampling the final token stream is
  byte-identical to the unpreempted run, and the caller observes
  exactly-once completion either way (the sealed flag is the single
  commit point). A model that generates by diffusion over blocks
  (``block_length``) resumes from its prompt and the whole blocks it
  has emitted, its half-made block thrown away;
- **deadline sweep**: every iteration seals requests whose inherited
  PR-7 budget died, typed, with the stage recorded.

All methods run on the engine loop thread except ``submit`` /
``seal`` which synchronize through the engine's lock.
"""

from __future__ import annotations

import itertools
import queue as queue_mod
import threading
import time
from collections import deque
from typing import Any

from ray_tpu.exceptions import CacheExhaustedError, TaskTimeoutError
from ray_tpu.serve.llm_engine.kv_cache import PagedKVCache
from ray_tpu.util import tracing

WAITING = "waiting"
PREFILL = "prefill"
DECODE = "decode"

#: Stage names a request's deadline can die at (the README deadline
#: semantics table documents both).
STAGE_QUEUE = "llm_queue"
STAGE_DECODE = "llm_decode"

#: In a block in flight (diffusion over blocks): no token fixed here yet.
MASKED = -1


class EngineRequest:
    """One generation request moving through the engine."""

    __slots__ = (
        "tokens", "max_new_tokens", "temperature", "deadline", "name",
        "state", "output", "block_table", "position", "context",
        "prefilled", "sample_first", "remaining", "last_token",
        "preempted", "sealed", "error", "done", "stream",
        "rid", "submitted_ns", "claimed_ns", "first_token_ns", "sealed_ns",
        "trace_ctx", "slot", "request_id",
        "block", "passes", "denoising_steps", "remasking",
        "confidence_threshold",
    )
    _rids = itertools.count()

    def __init__(self, tokens: "list[int]", max_new_tokens: int,
                 temperature: float, deadline: "float | None" = None,
                 name: str = "llm_generate", stream: bool = False,
                 denoising_steps: int = 1, remasking: str = "sequential",
                 confidence_threshold: float = 0.9,
                 request_id: "str | None" = None):
        self.tokens = list(tokens) or [0]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.deadline = deadline
        self.name = name
        self.state = WAITING
        self.output: "list[int]" = []
        self.block_table: "list[int]" = []
        # The row it holds while it prefills and decodes (its row of the
        # decode step and of every per-row cache); -1: none.
        self.slot = -1
        self.position = 0
        # Tokens to (re)prefill this attempt; recomputed on resume.
        self.context: "list[int]" = list(self.tokens)
        self.prefilled = 0
        # The first generated token is sampled from prefill logits on
        # the FIRST attempt only — a resumed request already knows it.
        self.sample_first = True
        self.remaining = int(max_new_tokens)
        self.last_token = 0
        # Generation by diffusion over blocks (``Scheduler.block_length``):
        # the block in flight from ``position`` on (``MASKED`` where no
        # token is fixed yet), the denoising passes it has had,
        # and the request's schedule: passes a block, the rule that
        # picks what a pass fixes, and the rule's threshold.
        self.block: "list[int]" = []
        self.passes = 0
        self.denoising_steps = int(denoising_steps)
        self.remasking = remasking
        self.confidence_threshold = float(confidence_threshold)
        self.preempted = 0
        self.sealed = False
        self.error: "Exception | None" = None
        self.done = threading.Event()
        # Streaming consumers read tokens as they are emitted;
        # bounded memory is max_new_tokens ints either way.
        self.stream: "queue_mod.SimpleQueue | None" = (
            queue_mod.SimpleQueue() if stream else None)
        self.rid = next(self._rids)
        # The streamed request this serves, as the serve tier names it
        # (``serve.replica.stream_request_id``): ``request`` on this
        # request's spans, so they pair with the hops around the engine.
        self.request_id = request_id
        # Where the request's time went, ``time.monotonic_ns()``: the
        # engine sums queue wait and prefill time into its counters
        # from these, and records them as spans at the seal while
        # tracing is armed. 0 = not reached.
        self.submitted_ns = time.monotonic_ns()
        self.claimed_ns = 0      # first claim only: a resume keeps it
        self.first_token_ns = 0
        self.sealed_ns = 0
        # The submitter's span context (None while tracing is off).
        self.trace_ctx = tracing.make_trace_context()

    def stage(self) -> str:
        return STAGE_QUEUE if self.state == WAITING else STAGE_DECODE


class Scheduler:
    """Owns the request queues and the paged-cache block accounting."""

    def __init__(self, cache: PagedKVCache, max_batch: int,
                 max_waiting: int, max_tokens_per_seq: int,
                 block_length: int = 0):
        self.cache = cache
        # > 0: generation by diffusion over blocks of so many positions.
        self.block_length = block_length
        self.max_batch = max_batch
        self.max_waiting = max_waiting
        self.max_tokens_per_seq = max_tokens_per_seq
        self.waiting: "deque[EngineRequest]" = deque()
        self.prefilling: "EngineRequest | None" = None
        self.active: "list[EngineRequest]" = []
        # Row slots: the prefilling request and the active ones hold one
        # each (a claim needs a free decode row, so there is always one).
        self._free_slots = list(range(max_batch - 1, -1, -1))

    # ------------------------------------------------------------ admission

    def try_enqueue(self, req: EngineRequest) -> None:
        """Bounded admission (caller holds the engine lock). Raises
        typed on a full queue or a request that could NEVER fit the
        pool — both shed through the SystemOverloadedError path."""
        if len(self.waiting) >= self.max_waiting:
            raise CacheExhaustedError(
                f"engine waiting queue full ({self.max_waiting})")
        total = min(len(req.tokens) + req.max_new_tokens,
                    self.max_tokens_per_seq)
        if not self.cache.fits_ever(total):
            raise CacheExhaustedError(
                f"request needs {self.cache.blocks_for_tokens(total)} "
                f"KV blocks; the pool holds "
                f"{self.cache.usable_blocks} — unservable at any load")
        self.waiting.append(req)

    def claim_prefill(self) -> "EngineRequest | None":
        """Move the head waiting request into the prefill seat when
        both the seat and a decode row are free."""
        if self.prefilling is not None or not self.waiting \
                or len(self.active) >= self.max_batch:
            return None
        req = self.waiting.popleft()
        self.prefilling = req
        req.slot = self._free_slots.pop()
        req.state = PREFILL
        req.prefilled = 0
        if not req.claimed_ns:
            req.claimed_ns = time.monotonic_ns()
        # Recompute-on-resume: re-prefill everything whose k/v the
        # preemption dropped — the prompt plus every generated token
        # except the last (its k/v is written by the NEXT decode step,
        # exactly as in the unpreempted trajectory).
        if self.block_length:
            # Diffusion over blocks: the whole blocks of what is known
            # (the prompt and the tokens emitted; a half-made block was
            # thrown away with the preemption) are prefilled, and what
            # is left over opens the block in flight as known positions.
            known = req.tokens + req.output
            whole = len(known) // self.block_length * self.block_length
            req.context = known[:whole]
            req.block = known[whole:] + [MASKED] * (
                whole + self.block_length - len(known))
            req.passes = 0
            req.sample_first = False  # prefill yields no token
        elif req.output:
            req.context = req.tokens + req.output[:-1]
            req.sample_first = False
            req.last_token = req.output[-1]
        else:
            req.context = list(req.tokens)
            req.sample_first = True
        return req

    def release(self, req: EngineRequest) -> None:
        """Give back what ``req`` holds: its blocks and its row slot
        (finish, preemption, shed, deadline expiry, shutdown)."""
        self.cache.release(req.block_table)
        if req.slot >= 0:
            self._free_slots.append(req.slot)
            req.slot = -1

    # ----------------------------------------------------------- preemption

    def pick_victim(self) -> "EngineRequest | None":
        """Lowest-progress active request (fewest generated tokens;
        ties toward the latest admit — it has the least sunk decode
        work and the freshest queue position)."""
        if not self.active:
            return None
        return min(self.active,
                   key=lambda r: (len(r.output), -r.rid))

    def preempt(self, victim: EngineRequest) -> None:
        """Release the victim's blocks and row slot and push it to the
        FRONT of the waiting queue (it resumes as soon as pressure
        eases)."""
        self.release(victim)
        if victim in self.active:
            self.active.remove(victim)
        if self.prefilling is victim:
            self.prefilling = None
        victim.state = WAITING
        victim.prefilled = 0
        victim.preempted += 1
        self.waiting.appendleft(victim)

    # ------------------------------------------------------------ deadlines

    def sweep_expired(self, now: "float | None" = None
                      ) -> "list[EngineRequest]":
        """Requests whose budget died (or that a caller-side wait
        already sealed): drop them from every seat, free their blocks,
        and return the ones THIS sweep must seal typed (already-sealed
        ones just need their blocks reclaimed)."""
        now = time.time() if now is None else now
        expired: "list[EngineRequest]" = []

        def dead(req: EngineRequest) -> bool:
            return req.sealed or (req.deadline is not None
                                  and now > req.deadline)

        for req in [r for r in self.waiting if dead(r)]:
            self.waiting.remove(req)
            expired.append(req)
        if self.prefilling is not None and dead(self.prefilling):
            expired.append(self.prefilling)
            self.prefilling = None
        for req in [r for r in self.active if dead(r)]:
            self.active.remove(req)
            expired.append(req)
        for req in expired:
            self.release(req)
        return [r for r in expired if not r.sealed]

    # -------------------------------------------------------------- queries

    def depth(self) -> int:
        """Requests the engine currently owns (the autoscaler's
        engine-depth signal)."""
        return (len(self.waiting) + len(self.active)
                + (1 if self.prefilling is not None else 0))

    def expired_error(self, req: EngineRequest) -> TaskTimeoutError:
        return TaskTimeoutError(req.name, req.stage(),
                                req.deadline or 0.0)
