"""The continuous-batching engine loop over the paged KV cache.

One thread per engine runs the scheduler's interleave: sweep expired
budgets, claim/advance ONE prefill chunk, then ONE fixed-shape decode
step for every active stream — tokens stream out per step, finished
rows free their blocks between steps, and cache pressure preempts the
lowest-progress stream (recompute-on-resume) instead of failing it.
A chunk's table is as wide as its request's needs, of three widths
(``table_widths``), and so is a step's, as wide as its longest live
row's, where the step gathers its width; a step that reads by row
(``Family.reads_by_row``) has the whole table. Each is a program the
constructor has built, and it builds no other.

The loop keeps one decode step ahead: step N+1 is launched on step N's
result where it lies on the device (the program's ``prev``), and only
then is N read and emitted, so the device runs N+1 while the host
fetches, emits, sweeps and launches a prefill chunk. At most ONE step is
in flight unread (``_Step``); a request's ``position``, ``last_token``,
``block`` and ``output`` move on at emission, and what lies ahead of
them is that record. Which rows N+1 carries, and where, the family
tells from that state by counting (``Family.lead``, ``row_of(req,
True)``): a row ends by its count or its table's end, and a row joins
from prefill with tokens the host has. Whatever needs the values reads
the step in flight first: a preemption rebuilds its victim's context
from ``output``, and a row says so itself (``Family.ahead``) where its
next pass hangs on them (a denoising pass under the dynamic rule).

The model's family (``model.family(config)``, looked up once) gives the
weights, the cache and the two programs: a stack of identical layers
over one paged pool, or a hybrid's three caches (``hybrid.py``: the one
full-attention pool, a ring of blocks a row for the window layers, a
recurrent state a row). A request holds a row slot from its claim to
its release: its row of the decode step and of every per-row cache.

The loop counts tokens, not steps: a pass of the decode program makes
0 or more tokens final for each busy row (``Family.advance``). An
autoregressive row yields one a pass. A model that generates by
diffusion over blocks (``config.block_length``) carries a block of
positions in flight: a denoising pass fixes some of them, the block's
tokens are emitted together once none is masked (a stream sees whole
blocks), and one more pass, which yields nothing, stores the block's
keys and values; its prefill yields no first token, and its table grows
a block at a time. Between passes the block stays on the device: how
many of its positions a pass fixes is known without their values.

Counters ship as ``ENGINE_STAT_KEYS`` through the node-stats heartbeat
piggyback (``ray_tpu_node_engine`` /metrics family) via the
process-local engine registry below.

On the record: every phase of a loop pass is a ``tracing.phase`` (an
``engine.iteration`` span with leaf spans ``engine.sweep``,
``engine.prefill.schedule`` / ``.launch`` / ``.first_token``,
``engine.decode.schedule`` / ``.launch`` / ``.fetch`` / ``.emit`` and
``engine.idle``), so a profiler session shows on the device trace's
clock what the host did in a device idle gap; the time counters
(``loop_wall_us`` ... ``decode_host_us``) say the same to ``/metrics``
without a session, and ``host_calls`` counts the calls into JAX, each
of which lets go of the interpreter; ``launches_starved`` counts the
launches that found the device drained (``starved`` on the two launch
spans), session or none; a leaf span that takes the engine's lock
carries ``lock_wait_us``, from asking to holding, and the collector's
pauses are the process's ``gc_pause_us``, ``gc_full_collections`` and
the span ``runtime.gc`` (``util/tracing.py:record_gc``); a request's
four stamps become the
spans ``llm.request`` > ``llm.queue`` / ``llm.prefill`` /
``llm.decode`` at its seal while tracing is armed.

Chaos: ``llm.slow_step`` wedges one decode step for
``RAY_TPU_LLM_SLOW_S`` seconds before the jitted call — the
deterministic proof that a wedged decode trips the request deadline
typed (caller-side seal, stage recorded) instead of hanging streams.
"""

from __future__ import annotations

import functools
import importlib
import os
import sys
import threading
import time
import weakref

import numpy as np

from ray_tpu._private import chaos, jax_compat, lock_witness
from ray_tpu.exceptions import CacheExhaustedError, GetTimeoutError
from ray_tpu.models.moe import init_stats, read_stats
from ray_tpu.serve.llm_engine import model as paged_model
from ray_tpu.serve.llm_engine.kv_cache import PagedKVCache
from ray_tpu.serve.llm_engine.scheduler import (
    DECODE,
    EngineRequest,
    Scheduler,
)
from ray_tpu.util import tracing

__all__ = ["ENGINE_STAT_KEYS", "LLMEngine",
           "merged_engine_stats", "merged_engine_load"]

# Counter contract: code increments exactly these keys, engine_stats()
# serves them, the README "LLM serving" section documents them, and
# metrics_agent exports them as the ray_tpu_node_engine family (the
# counter-keys analysis pass enforces all three).
ENGINE_STAT_KEYS = (
    "admitted", "shed_queue_full", "shed_cache",
    "prefill_chunks", "prefill_tokens",
    # ``decode_tokens``: tokens the decode passes made final and
    # emitted (an autoregressive row yields one a step, so there: the
    # rows of the steps, less a row sealed while its step ran).
    # ``block_rows``: busy rows summed over the passes; ``commit_rows``:
    # those of them on a finishing pass of diffusion over blocks, which
    # stores a whole block's keys and values and yields no token.
    "decode_steps", "batched_decode_steps", "decode_tokens",
    "block_rows", "commit_rows",
    "preemptions", "resumes", "finished", "deadline_expired",
    "slow_steps", "blocks_allocated", "blocks_freed",
    # Where the time went, in microseconds, summed on the engine
    # thread: per request up to its first token, and per loop pass
    # that made progress.
    "first_tokens", "queue_wait_us", "prefill_us",
    "loop_wall_us", "loop_cpu_us", "fetch_wait_us", "decode_host_us",
    # The CPU time of the whole process (``time.process_time_ns()``)
    # when the counters are asked for: against ``loop_cpu_us`` over the
    # same interval, the share of the process's CPU that the engine
    # thread gets; the rest is the stream and client threads, the
    # runtime's and the background.
    "process_cpu_us",
    # Calls the engine thread made into JAX (a dispatch with its one
    # host array, a blocking read): two per decode step, one per
    # prefill chunk, and the first token's sampling and read.
    "host_calls",
    # What a sparse model's routing did (models/moe.py), summed over
    # layers and steps ON THE DEVICE in an array the jitted steps carry;
    # read from it when engine_stats() is asked, by no step. Zero for a
    # dense model. (moe.EXPERT_COUNTERS, spelled out for the
    # counter-keys pass, which reads this tuple's literals.)
    "expert_choices", "expert_slots", "experts_touched",
    "expert_peak_choices",
    # Summed over decode steps: positions the rows' contexts hold in
    # the full-attention pool, and positions the step read of it (the
    # step's table width for every row where it gathers; the busy rows'
    # own pages and fresh entries where ``Family.reads_by_row``). Then
    # a hybrid model's per-row caches: blocks of a window ring written
    # over with newer positions, and recurrent states started from zero
    # (a request's first chunk, and its first again after a preemption).
    "kv_positions_live", "kv_positions_read",
    "window_blocks_recycled", "state_resets",
    # Decode steps run at a table narrower than the whole
    # (``table_widths``): how often the width followed the context.
    # 0 where the step reads by row: its one width is the whole.
    "decode_steps_narrow",
    # Decode steps launched while the step before was still unread, on
    # its tokens where they lay on the device.
    "decode_steps_ahead",
    # Decode steps and prefill chunks launched onto a device that had
    # already finished everything launched before (``_drained``): the
    # chip stood until that launch landed. ``ahead`` says the host did
    # not wait for a step's tokens; this says the chip did wait for the
    # host. Once a request by construction: the first step after its
    # first token's blocking read.
    "launches_starved",
    # The collector's pauses, the whole process's as ``process_cpu_us``
    # is, read when the counters are asked for
    # (``tracing.gc_counters``): wall time inside collections of every
    # generation, during which every thread stands, and the number of
    # full ones (generation 2).
    "gc_pause_us", "gc_full_collections",
)

# The engine thread lets go of the interpreter inside every program call
# and every read of its tokens, several times a step. To get it back it
# waits for whichever thread is running Python to give it up, which a
# thread that does not block does only when the switch interval is over:
# at the default 5 ms, with a hundred stream and client threads in the
# process, ``engine.decode.launch`` took 31 ms a step for 1 to 2 (the
# traced host plane of a slow run, PR 33). A process that hosts an engine
# of ``_MANY_ROWS`` rows or more hands the interpreter over at most this
# long after it is asked for: at 32 rows (a hundred and fifty threads)
# that took the slow phases away (six runs within 1.2%); at 16 rows,
# where the launch waits 1 to 3 ms, it cost 8% of the tokens a second
# (634 and 646 for 695), so fewer rows leave the interpreter alone.
_SWITCH_INTERVAL_S = 0.0005
_MANY_ROWS = 32

# Live engines in THIS process (serve replicas are co-hosted with the
# node executor, so daemon heartbeats pick these up; driver-local
# engines surface under node="driver" in the scrape).
_LIVE: "weakref.WeakSet" = weakref.WeakSet()


def table_widths(blocks_per_seq: int) -> "tuple[int, ...]":
    """The table widths a prefill chunk or a decode step that gathers
    may be given, in blocks, narrowest first: a quarter, a half and the
    whole of a row's table, each in whole blocks. A chunk gathers and
    attends over its whole width, and so does every row of a step of a
    family that gathers (a hybrid's, a pass of diffusion over blocks),
    so a chunk takes the narrowest that holds its request's table and
    such a step the narrowest that holds its longest live one (at 128
    tokens a chunk's float32 scores over Mistral's whole table are 33 MB
    a layer: 12.81 ms a chunk for 12.24 at the quarter, PR 38). A step
    that reads each row's own pages through the tables
    (``Family.reads_by_row``) reads the same at every width: it has one
    program, at the whole table (``LLMEngine._step_widths``).
    Three, because each is a program built before the engine serves:
    halving twice keeps the read within twice the longest context down
    to a quarter of the table, and a further rung would add a compile
    for ever less. A table under four blocks has the one width."""
    if blocks_per_seq < 4:
        return (blocks_per_seq,)
    return (-(-blocks_per_seq // 4), -(-blocks_per_seq // 2), blocks_per_seq)


def default_prefill_chunk(max_tokens: int, block_size: int) -> int:
    """The prefill chunk an engine is given no length for:
    ``llm_prefill_chunk``, no longer than a row's table
    (``max_tokens``), in whole paged blocks (and so in whole blocks of a
    diffusion model, which the constructor holds to divide a paged
    block)."""
    from ray_tpu._private.config import GLOBAL_CONFIG

    chunk = min(int(GLOBAL_CONFIG.llm_prefill_chunk), max_tokens)
    return max(chunk // block_size, 1) * block_size


class _PassClock:
    """Of the loop pass under way, the engine thread's alone: ns spent
    blocked on the device's answer, and whether a decode step ran."""

    __slots__ = ("fetch_ns", "decoded")


class _Step:
    """A decode step launched and not yet read, the engine thread's
    alone: its rows as scheduled (``active`` and the row ``slots`` they
    held), its host array (``rows``) at its table ``width``, the
    positions its rows' contexts hold (``live``) and those it reads of
    the pool (``read``), whether the step before it was unread at its
    launch (``ahead``); from the launch on whether it found the device
    drained (``starved``), its tokens on the device (``out``) and the
    sampling key it returned, split again by any first token sampled
    since (``key``)."""

    __slots__ = ("active", "slots", "rows", "width", "live", "read",
                 "ahead", "starved", "out", "key")

    def __init__(self, active, slots, rows, width, live, read, ahead):
        self.active, self.slots, self.rows = active, slots, rows
        self.width, self.live, self.read = width, live, read
        self.ahead = ahead


class _Held:
    """The engine's lock, taken inside the leaf span ``span``: while
    the span is live the time from asking to holding goes on it as
    ``lock_wait_us`` (two ``monotonic_ns``); otherwise no clock is
    read."""

    __slots__ = ("_lock", "_span")

    def __init__(self, lock, span):
        self._lock, self._span = lock, span

    def __enter__(self) -> None:
        if not self._span.live:
            self._lock.acquire()
            return
        asked = time.monotonic_ns()
        self._lock.acquire()
        self._span.set(lock_wait_us=(time.monotonic_ns() - asked) // 1000)

    def __exit__(self, exc_type, exc, tb) -> None:
        self._lock.release()


#: ``_grow_or_preempt_locked``: the table cannot grow without a victim
#: while a step is in flight unread; read that step, then ask again.
_UNREAD = "unread"


class LLMEngine:
    """Paged-KV continuous-batching engine (token-in/token-out).

    ``params``: the family's ``init_params`` tree (any dtype; cast by
    ``model.serving_params``), a tree an engine already holds
    (``engine.params``), or None to build the weights here from
    ``seed``. The engine HOLDS, and every program takes, the family's
    laying of it (``Family.lay_params``; the paged and block families:
    ``model.lay_for_serving``, a layer's ``wq``, ``wk`` and ``wv`` as
    one ``wqkv [n, E, (H + 2 KV) D]``, which a step reads where it lies
    in the stack): ``engine.params`` is that tree, each weight on the
    device once. A caller's arrays are never donated or changed."""

    def __init__(self, config=None, params=None, *,
                 max_batch_size: int = 8, max_seq_len: "int | None" = None,
                 block_size: "int | None" = None,
                 num_blocks: "int | None" = None,
                 prefill_chunk: "int | None" = None,
                 max_waiting: "int | None" = None,
                 seed: int = 0, mesh=None):
        import jax

        from ray_tpu._private.config import GLOBAL_CONFIG
        from ray_tpu.models import llama

        self.config = config or llama.LlamaConfig.tiny()
        self._family = paged_model.family(self.config)
        if self._family.reads_by_row:
            # Its step reads through a pallas kernel, whose modules take
            # a second to import: on a thread, beside the weights' program.
            threading.Thread(target=importlib.import_module, daemon=True,
                             args=("jax.experimental.pallas.tpu",)).start()
        # In the layout the family's programs take, before anything else
        # is on the device.
        self.params = paged_model.serving_params(self.config, params, seed,
                                                 laid=True)
        self.max_batch = int(max_batch_size)
        self.max_len = int(max_seq_len or self.config.max_seq_len)
        self.block_size = int(block_size or GLOBAL_CONFIG.llm_block_size)
        # Table width: blocks covering max_len, rounded up. A prefill
        # chunk takes the narrowest of ``_widths`` that holds its
        # request's table and a decode step the narrowest of
        # ``_step_widths`` that holds its longest row's, so each
        # program exists once a width it can be given, at [max_batch or
        # 1, width * block_size] attention width. A step that reads by
        # row reads the same at any width: it has the whole table.
        self.blocks_per_seq = -(-self.max_len // self.block_size)
        self.max_tokens = self.blocks_per_seq * self.block_size
        self._widths = table_widths(self.blocks_per_seq)
        self._step_widths = self._widths[-1:] \
            if self._family.reads_by_row else self._widths
        self.prefill_chunk_len = int(prefill_chunk or default_prefill_chunk(
            self.max_tokens, self.block_size))
        if num_blocks is None:
            # Default pool: every row can hold a full-length sequence
            # (+ scratch). Smaller pools oversubscribe and lean on
            # preemption — the production configuration.
            num_blocks = 1 + self.max_batch * self.blocks_per_seq
        cache = PagedKVCache(int(num_blocks), self.block_size,
                             self.blocks_per_seq)
        # Positions a busy row's pass writes: one, or (diffusion over
        # blocks) its block, which lies inside one paged block and one
        # prefill chunk.
        block_length = int(getattr(self.config, "block_length", 0))
        self._span = block_length or 1
        if self.block_size % self._span \
                or self.prefill_chunk_len % self._span:
            raise ValueError(
                f"block_length {self._span} must divide block_size "
                f"{self.block_size} and prefill_chunk "
                f"{self.prefill_chunk_len}")
        self._sched = Scheduler(
            cache, self.max_batch,
            int(max_waiting or GLOBAL_CONFIG.llm_max_waiting),
            self.max_tokens, block_length)
        self._mesh = mesh
        self._pool = self._new_pool()
        # Positions a row of the family's window ring holds (0: none).
        self._ring = self._family.ring_positions(
            self.config, self.block_size, self.prefill_chunk_len)
        # The expert counters' accumulator: an argument and a result
        # of every step, never donated, so a reader on another thread
        # holds an array that stays valid. None for a dense model.
        self._expert_stats = init_stats() \
            if self.config.num_experts > 0 else None
        # The sampling key lives on the device: the decode program
        # splits it and returns the carry. Made by a program under the
        # engine's mesh, it is placed as that carry will be, so the
        # first step's program is every later step's too.
        # ``_key`` is the key of the last step whose tokens were read;
        # the step in flight (``_unread``) holds the one it returned.
        # ``_no_prev`` is what a step with no step before it is given
        # for ``prev``: made as the key is, so placed as a step's first
        # result, and of its shape (a token a row, or a block).
        shape = (self.max_batch, block_length) if block_length \
            else (self.max_batch,)
        with jax_compat.set_mesh(mesh):
            self._key, self._no_prev = jax.jit(lambda: (
                jax.random.PRNGKey(seed + 1),
                jax.numpy.zeros(shape, "int32")))()
        self._unread: "_Step | None" = None
        self._counters: "dict[str, int]" = {k: 0 for k in ENGINE_STAT_KEYS}
        self._pass = _PassClock()
        self._lock = lock_witness.Condition("llm_engine.LLMEngine.state")
        self._shutdown = threading.Event()
        if self.max_batch >= _MANY_ROWS \
                and sys.getswitchinterval() > _SWITCH_INTERVAL_S:
            sys.setswitchinterval(_SWITCH_INTERVAL_S)
        self._build_programs()
        _LIVE.add(self)
        tracing.record_gc(self)
        self._loop_thread = threading.Thread(
            target=self._engine_loop, name="llm-paged-engine", daemon=True)
        self._loop_thread.start()

    # ----------------------------------------------------------- jitted fns

    @functools.cached_property
    def _decode_step(self):
        return self._family.make_engine_decode_step(
            self.config, self.block_size)

    @functools.cached_property
    def _prefill_step(self):
        return self._family.make_engine_prefill_chunk(
            self.config, self.block_size, self.prefill_chunk_len)

    def _build_programs(self) -> None:
        """Each program at every width it can be given (``_widths``,
        ``_step_widths``), before the loop takes a request: a width
        first met while serving would compile while rows wait. The
        decode program is run once on rows that are all
        inactive (they write the scratch block, advance no state, and
        their samples are thrown away), the prefill program on a chunk
        that is all padding (it writes the scratch block, no ring, and
        zeros over the zeroed state of row slot 0); the key and the expert
        counters the engine holds are neither donated nor replaced, so
        a seeded request samples what it would have without these runs.
        Built in turn: from a warm cache a width costs 0.4 s, nearly
        all of it tracing in Python, which threads do not share (three
        at once took 1.7 s for 1.2 on the chip, PR 34). Lowered and
        compiled by name first: the call then finds the program, and
        the two together take 0.4 s where the call alone took 0.6."""
        for width in self._widths:
            if width in self._step_widths:
                rows = self._family.pack_decode_rows(
                    self.max_batch, width, ())
                args = (self.params, self._pool, rows, self._key,
                        self._expert_stats, self._no_prev)
                with jax_compat.set_mesh(self._mesh):
                    self._decode_step.lower(*args).compile()
                    _, self._pool, _, _ = self._decode_step(*args)
            chunk = self._family.pack_prefill_chunk(
                self.prefill_chunk_len, width, (), 0, (), 0)
            args = (self.params, self._pool, chunk, self._expert_stats)
            with jax_compat.set_mesh(self._mesh):
                self._prefill_step.lower(*args).compile()
                _, self._pool, _ = self._prefill_step(*args)

    @staticmethod
    def _rung(widths, blocks: int) -> int:
        """The narrowest of ``widths`` that holds a table of ``blocks``:
        what a prefill chunk (of ``_widths``, its request's) and a
        decode step (of ``_step_widths``, its longest live row's) are
        given."""
        return next(w for w in widths if w >= blocks)

    def _new_pool(self) -> dict:
        """The family's cache, zeroed: one dict, donated to every step.
        Made by a program under the engine's mesh, as the key is, so it
        is placed as the steps return it: the program a fresh pool
        meets is the one every later pool meets."""
        import jax

        with jax_compat.set_mesh(self._mesh):
            return jax.jit(lambda: self._family.init_cache(
                self.config, self._sched.cache.num_blocks, self.block_size,
                self.max_batch, self.prefill_chunk_len))()

    def _recycled(self, start: int, end: int) -> int:
        """Blocks of a row's window ring that writing positions
        ``start..end - 1`` begins to write over."""
        if not self._ring:
            return 0
        bs = self.block_size
        return max(0, -(-end // bs) - -(-max(start, self._ring) // bs))

    # ----------------------------------------------------------- public API

    def submit(self, tokens, max_new_tokens: int = 16,
               temperature: float = 0.0,
               deadline: "float | None" = None, stream: bool = False,
               name: str = "llm_generate",
               denoising_steps: "int | None" = None,
               remasking: "str | None" = None,
               confidence_threshold: "float | None" = None,
               request_id: "str | None" = None
               ) -> EngineRequest:
        """Admit one request (bounded; full queue / never-fits sheds
        typed through the SystemOverloadedError path). ``deadline`` is
        ABSOLUTE (time.time()); inherit it from the serve call via
        ``get_runtime_context().get_task_deadline()``. The last three
        are a request's schedule under diffusion over blocks (passes a
        block, ``model.REMASKING``, the dynamic rule's threshold); left
        out, the model configuration's; of no use to another model.
        ``request_id`` names the request on its spans (``request``)."""
        max_new = max(1, min(int(max_new_tokens), self.max_tokens - 2))
        prompt = list(tokens) or [0]
        keep = max(1, self.max_tokens - max_new - 1)
        prompt = prompt[-keep:]
        schedule = {}
        if self._span > 1:
            schedule = {
                "denoising_steps": self.config.denoising_steps
                if denoising_steps is None else int(denoising_steps),
                "remasking": remasking or self.config.remasking,
                "confidence_threshold": self.config.confidence_threshold
                if confidence_threshold is None
                else float(confidence_threshold)}
            if schedule["remasking"] not in paged_model.REMASKING:
                raise ValueError(
                    f"remasking={schedule['remasking']!r}: one of "
                    f"{paged_model.REMASKING}")
        req = EngineRequest(prompt, max_new, temperature,
                            deadline=deadline, name=name, stream=stream,
                            request_id=request_id, **schedule)
        with self._lock:
            if self._shutdown.is_set():
                raise RuntimeError("LLM engine is shut down")
            sched = self._sched
            if len(sched.waiting) >= sched.max_waiting:
                self._counters["shed_queue_full"] += 1
                raise CacheExhaustedError(
                    f"engine waiting queue full ({sched.max_waiting})")
            if not sched.cache.fits_ever(
                    min(len(prompt) + max_new, self.max_tokens)):
                self._counters["shed_cache"] += 1
                raise CacheExhaustedError(
                    f"request needs more KV blocks than the pool holds "
                    f"({sched.cache.usable_blocks})")
            sched.try_enqueue(req)
            self._counters["admitted"] += 1
            self._lock.notify_all()
        return req

    def result(self, req: EngineRequest,
               timeout_s: "float | None" = None) -> "list[int]":
        """Block until the request seals; a dead inherited budget seals
        it typed HERE (exactly once, even when the engine loop itself
        is wedged — the chaos llm.slow_step contract)."""
        wall_deadline = (time.monotonic() + timeout_s
                         if timeout_s is not None else None)
        while not req.done.wait(timeout=0.05):
            self._check_caller_deadline(req)
            if wall_deadline is not None \
                    and time.monotonic() > wall_deadline:
                raise GetTimeoutError(
                    f"generation exceeded timeout_s={timeout_s}")
        if req.error is not None:
            raise req.error
        return list(req.output)

    def stream_tokens(self, req: EngineRequest):
        """Yield tokens AS the engine emits them (consumption overlaps
        decode). Terminates with the sealed result: StopIteration on
        success, the typed error otherwise."""
        for tokens in self.stream_token_batches(req):
            yield from tokens

    def stream_token_batches(self, req: EngineRequest):
        """``stream_tokens`` for a consumer that pays per delivery: yields
        LISTS, each the tokens that were waiting when it looked (one
        while the consumer keeps up; more once it has fallen behind)."""
        import queue as queue_mod

        assert req.stream is not None, "submit(stream=True) first"
        while True:
            try:
                kind, payload, emitted_ns = req.stream.get(timeout=0.05)
            except queue_mod.Empty:
                self._check_caller_deadline(req)
                continue
            # The hand-off from the engine thread's emission to this
            # stream thread, from its wake-up to the yield; age_us: how
            # long the oldest token it takes had waited by then.
            with tracing.phase("llm.stream.take") as hop:
                age_us = tracing.age_us(emitted_ns) if hop.live else None
                tokens = []
                while kind == "tok":
                    tokens.append(payload)
                    try:
                        kind, payload, _ = req.stream.get_nowait()
                    except queue_mod.Empty:
                        kind = None
                if hop.live:
                    hop.set(request=req.request_id, tokens=len(tokens),
                            age_us=age_us)
            if tokens:
                yield tokens
            if kind == "end":
                return
            if kind == "err":
                raise payload

    def _check_caller_deadline(self, req: EngineRequest) -> None:
        if req.deadline is not None and time.time() > req.deadline \
                and not req.sealed:
            if self._seal(req, self._sched.expired_error(req)):
                with self._lock:
                    self._counters["deadline_expired"] += 1

    # -------------------------------------------------------------- sealing

    def _seal(self, req: EngineRequest,
              error: "Exception | None" = None) -> bool:
        """The ONE commit point: first sealer wins (engine finish,
        engine/caller deadline sweep, shutdown) — completion is
        exactly-once however the race lands, preempted or not."""
        with self._lock:
            if req.sealed:
                return False
            req.sealed = True
            req.error = error
        self._record_sealed(req)
        if req.stream is not None:
            req.stream.put(("err", error, 0) if error is not None
                           else ("end", None, 0))
        req.done.set()
        return True

    @staticmethod
    def _record_sealed(req: EngineRequest) -> None:
        """Stamp the seal; while tracing is armed, the request's
        stamps become spans under the submitter's span: ``llm.request``
        over ``llm.queue`` (submit to first claim), ``llm.prefill`` (to
        the first token) and ``llm.decode`` (to the seal), as far as
        the request got."""
        req.sealed_ns = time.monotonic_ns()
        if not tracing.TRACE_ON or req.trace_ctx is None:
            return
        trace_id, parent_id, _ = req.trace_ctx
        # What ties these to the hops of the serve tier around them.
        tie = {"request": req.request_id} if req.request_id else {}
        now = time.time()
        to_wall = now - req.sealed_ns / 1e9  # monotonic -> wall clock
        root = tracing.record_span(
            "llm.request", to_wall + req.submitted_ns / 1e9, now,
            trace_id, parent_id,
            {"req": req.rid, "prompt_tokens": len(req.tokens),
             "new_tokens": len(req.output), "preempted": req.preempted,
             **tie,
             **({"error": type(req.error).__name__}
                if req.error is not None else {})})
        stamps = (req.submitted_ns, req.claimed_ns, req.first_token_ns,
                  req.sealed_ns)
        for name, start, end in zip(
                ("llm.queue", "llm.prefill", "llm.decode"),
                stamps, stamps[1:]):
            if start:
                tracing.record_span(
                    name, to_wall + start / 1e9,
                    to_wall + (end or req.sealed_ns) / 1e9, trace_id,
                    root, {"req": req.rid, **tie})

    def _deliver_locked(self, req: EngineRequest, tokens: list) -> bool:
        """Emit the tokens a pass made final for ``req``, in order; the
        request's first are stamped. True when the request is over: its
        limit reached, or its table's end."""
        if tokens and req.stream is not None:
            # When the engine thread let go of them (0: no sink live);
            # the stamp stays on this side of the stream's yield.
            emitted_ns = tracing.stamp_ns()
            for token in tokens:
                req.stream.put(("tok", token, emitted_ns))
        req.output.extend(tokens)
        req.remaining = req.max_new_tokens - len(req.output)
        if tokens and not req.first_token_ns:
            req.first_token_ns = time.monotonic_ns()
            counters = self._counters
            counters["first_tokens"] += 1
            counters["queue_wait_us"] += \
                (req.claimed_ns - req.submitted_ns) // 1000
            counters["prefill_us"] += \
                (req.first_token_ns - req.claimed_ns) // 1000
        return req.remaining <= 0 or req.position >= self.max_tokens

    # --------------------------------------------------------------- engine

    def _engine_loop(self) -> None:
        while not self._shutdown.is_set():
            self._iteration()

    def _iteration(self) -> None:
        """One pass of the loop, on the record: its phases as spans,
        and, where it made progress, its wall and thread CPU time in
        the counters (a thread that is on the CPU for less than its
        wall time outside the device waits was waiting for the
        interpreter or the engine's lock)."""
        wall0, cpu0 = time.monotonic_ns(), time.thread_time_ns()
        clock = self._pass
        clock.fetch_ns, clock.decoded = 0, False
        # An empty engine's passes stay out of the span buffer.
        phase = tracing.phase if self._sched.depth() \
            else tracing.profiler_phase
        with phase("engine.iteration"):
            with phase("engine.sweep") as sweep:
                with _Held(self._lock, sweep):
                    newly_expired = self._sched.sweep_expired()
                    for req in newly_expired:
                        self._counters["deadline_expired"] += 1
                for req in newly_expired:
                    self._seal(req, self._sched.expired_error(req))
                sweep.set(expired=len(newly_expired))
            progressed = self._prefill_tick()
            progressed = self._decode_tick() or progressed
            if not progressed:
                with phase("engine.idle"), self._lock:
                    if self._sched.depth() == 0:
                        self._lock.wait(0.002)
                return
        # The CPU interval lies inside the wall interval: cpu <= wall.
        cpu = (time.thread_time_ns() - cpu0) // 1000
        wall = (time.monotonic_ns() - wall0) // 1000
        fetch = clock.fetch_ns // 1000
        counters = self._counters
        counters["loop_wall_us"] += wall
        counters["loop_cpu_us"] += cpu
        counters["fetch_wait_us"] += fetch
        if clock.decoded:
            counters["decode_host_us"] += wall - fetch

    def _grow_or_preempt_locked(self, req: EngineRequest,
                                n_tokens: int) -> str:
        """Grow ``req``'s table to cover ``n_tokens``, preempting the
        lowest-progress stream per retry (caller holds the lock).
        Returns ``"ok"`` when the table covers the target,
        ``"victim"`` when ``req`` itself was preempted, ``"shed"``
        when nothing was left to preempt (the caller seals typed,
        OUTSIDE the lock), and ``_UNREAD`` before any of that while a
        decode step is in flight: a victim resumes from its ``output``,
        which must hold every token the device has made, and that
        step's emission may free the blocks (the caller reads it,
        OUTSIDE the lock, and asks again)."""
        while True:
            try:
                self._sched.cache.grow(req.block_table, n_tokens)
                return "ok"
            except CacheExhaustedError:
                if self._unread is not None:
                    return _UNREAD
                victim = self._sched.pick_victim()
                if victim is None and self._sched.prefilling is req:
                    # No decode stream left to preempt and the pool
                    # still can't take the prefill: shed typed (only
                    # reachable while sealed-but-unswept holders pin
                    # blocks — the next sweep frees them).
                    self._sched.prefilling = None
                    self._sched.release(req)
                    self._counters["shed_cache"] += 1
                    return "shed"
                if victim is None:
                    victim = req
                self._counters["preemptions"] += 1
                self._sched.preempt(victim)
                if victim is req:
                    return "victim"

    def _prefill_tick(self) -> bool:
        """At most ONE chunk of ONE request per engine iteration —
        the interleave that keeps long prompts from stalling decode."""
        if self._sched.prefilling is None and not self._sched.waiting:
            return False  # only this thread claims: nothing to open
        status = _UNREAD
        while status == _UNREAD:
            with tracing.phase("engine.prefill.schedule") as span, \
                    _Held(self._lock, span):
                if self._sched.prefilling is None:
                    claimed = self._sched.claim_prefill()
                    if claimed is not None and claimed.preempted > 0:
                        self._counters["resumes"] += 1
                req = self._sched.prefilling
                if req is None:
                    return False
                span.set(req=req.rid)
                n = min(self.prefill_chunk_len,
                        len(req.context) - req.prefilled)
                status = self._grow_or_preempt_locked(req,
                                                      req.prefilled + n)
                if status == "ok":
                    start = req.prefilled
                    table = list(req.block_table)
                    # As far as the request has it now.
                    width = self._rung(self._widths, len(table))
            if status == _UNREAD and not self._read_unread():
                return True  # the read failed: every request with it
        if status == "shed":
            self._seal(req, CacheExhaustedError(
                "KV block pool exhausted mid-prefill"))
            return True
        if status == "victim":
            return True  # re-queued; pressure eased — progress made
        if n == 0:
            # Diffusion over blocks, a prompt shorter than one block:
            # nothing to prefill, all of it opens the block in flight.
            return self._enter_decode(req, None)

        with tracing.phase("engine.prefill.launch", req=req.rid,
                           tokens=n) as launch:
            chunk = self._family.pack_prefill_chunk(
                self.prefill_chunk_len, width,
                req.context[start:start + n], start, table, req.slot)
            starved = self._drained()
            launch.set(starved=starved)
            try:
                with jax_compat.set_mesh(self._mesh):
                    last_logits, self._pool, self._expert_stats = \
                        self._prefill_step(self.params, self._pool, chunk,
                                           self._expert_stats)
            except Exception as exc:  # noqa: BLE001 — donated pool is gone
                self._reset_after_failure(exc)
                return True
            with self._lock:
                self._counters["host_calls"] += 1
                self._counters["prefill_chunks"] += 1
                self._counters["launches_starved"] += starved
                self._counters["prefill_tokens"] += n
                self._counters["window_blocks_recycled"] += \
                    self._recycled(start, start + n)
                if start == 0 and self._family.recurrent:
                    self._counters["state_resets"] += 1
                req.prefilled += n
                if req.prefilled < len(req.context):
                    return True
        return self._enter_decode(req, last_logits)

    def _enter_decode(self, req: EngineRequest, last_logits) -> bool:
        """The context is prefilled: the request enters the decode
        batch, with its first token where prefill yields one (not under
        diffusion over blocks, and not on a resume)."""
        with tracing.phase("engine.prefill.first_token", req=req.rid) \
                as first_token, _Held(self._lock, first_token):
            if first_token.live:
                first_token.set(request=req.request_id)
            req.position = len(req.context)
            first = []
            if req.sample_first:
                first = [self._sample_first(req, last_logits)]
            if self._span == 1:
                req.last_token = (first or req.output)[-1]
            self._sched.prefilling = None
            req.state = DECODE
            if self._deliver_locked(req, first):
                self._finish_locked(req)
            else:
                self._sched.active.append(req)
        return True

    def _sample_first(self, req: EngineRequest, last_logits) -> int:
        import jax
        import jax.numpy as jnp

        if req.temperature > 0:
            # Off the head of the key's chain, which the step in
            # flight holds while one is unread.
            step = self._unread
            if step is None:
                self._key, sub = jax.random.split(self._key)
            else:
                step.key, sub = jax.random.split(step.key)
            token = jax.random.categorical(
                sub, last_logits / max(req.temperature, 1e-4))
        else:
            token = jnp.argmax(last_logits)
        # Host-dispatched, once a request: the argmax, or the split,
        # its unpacking, the division and the draw; then the read.
        self._counters["host_calls"] += 5 if req.temperature > 0 else 2
        return self._fetch(int, token)

    def _fetch(self, read, array):
        """``read(array)`` blocks until the device has the answer: the
        time inside it is the pass's device wait, not host work."""
        t0 = time.monotonic_ns()
        out = read(array)
        self._pass.fetch_ns += time.monotonic_ns() - t0
        return out

    def _finish_locked(self, req: EngineRequest) -> None:
        self._sched.release(req)
        if req in self._sched.active:
            self._sched.active.remove(req)
        self._counters["finished"] += 1
        # Seal outside the engine lock is the usual discipline, but
        # _seal re-checks under the same reentrant-safe path; here we
        # mark and set the event after releasing blocks.
        req.sealed = True
        self._record_sealed(req)
        if req.stream is not None:
            req.stream.put(("end", None, 0))
        req.done.set()

    def _decode_tick(self) -> bool:
        """Launch the next decode step, then read the one BEFORE it,
        which was in flight unread: the new one stays in flight while
        the host emits, sweeps and runs a prefill chunk."""
        if self._unread is None and not self._sched.active:
            return False
        planned = _UNREAD
        while planned is _UNREAD:
            with tracing.phase("engine.decode.schedule") as span, \
                    _Held(self._lock, span):
                planned = self._plan_step_locked()
                if isinstance(planned, _Step):
                    span.set(rows=len(planned.active))
            if planned is _UNREAD and not self._read_unread():
                return True  # the read failed: every request with it
        step, before = planned, self._unread
        if step is not None:
            self._maybe_chaos_slow_step()
            try:
                with tracing.phase("engine.decode.launch",
                                   rows=len(step.active)) as launch, \
                        jax_compat.set_mesh(self._mesh):
                    step.starved = self._drained()
                    launch.set(starved=step.starved)
                    step.out, self._pool, self._expert_stats, step.key = \
                        self._decode_step(
                            self.params, self._pool, step.rows,
                            self._key if before is None else before.key,
                            self._expert_stats,
                            self._no_prev if before is None else before.out)
            except Exception as exc:  # noqa: BLE001 — donated pool is gone
                self._reset_after_failure(exc)
                return True
        self._unread = step
        if before is not None:
            self._read_step(before)
        return True

    def _drained(self) -> int:
        """1 where the device has finished everything this engine
        launched: asked just before a launch, it says the chip stands
        until that launch lands. Every program takes the cache and
        hands it back, so the cache the engine holds is a result of the
        newest one launched, of either kind; its first array is asked
        ``is_ready()``, which does not wait (and holds on to the
        interpreter: it is no call of ``host_calls``)."""
        return int(next(iter(self._pool.values())).is_ready())

    def _plan_step_locked(self):
        """The next decode step (caller holds the lock): a ``_Step``
        to launch; None when no row has a pass to run; ``_UNREAD`` when
        the step in flight has to be read first: a table cannot grow, or
        a row of it cannot tell its next pass by counting
        (``Family.ahead``). A row of the step in flight is planned one
        pass on, at the position that step moves it to
        (``Family.lead``), unless that step is its last."""
        unread, span, family = self._unread, self._span, self._family
        # Grow every row's table for what its pass is about to write.
        if unread is None:
            # Pressure preempts lowest-progress rows.
            for req in list(self._sched.active):
                if req not in self._sched.active:
                    continue  # already preempted as a victim
                self._grow_or_preempt_locked(req, req.position + span)
            active = list(self._sched.active)
            ahead = [False] * len(active)
            positions = [req.position for req in active]
        else:
            # Nothing is preempted while a step is unread: pressure
            # asks for its read.
            active, ahead, positions = [], [], []
            flying = set(unread.active)
            for req in self._sched.active:
                position, flies = req.position, req in flying
                if flies:
                    if not family.ahead(req):
                        return _UNREAD
                    lead = family.lead(req, self.max_tokens)
                    if lead is None:
                        continue
                    position += lead
                if self._grow_or_preempt_locked(
                        req, position + span) == _UNREAD:
                    return _UNREAD
                active.append(req)
                ahead.append(flies)
                positions.append(position)
        if not active:
            return None  # none goes on, or everything preempted
        # As held now: a row sealed while the step runs loses its.
        slots = [req.slot for req in active]
        longest = max(len(req.block_table) for req in active)
        width = self._rung(self._step_widths, longest)
        rows = family.pack_decode_rows(
            self.max_batch, width, map(family.row_of, active, ahead), slots)
        live = sum(positions) + span * len(active)
        block = self.block_size
        if family.reads_by_row:
            # Whole pages up to the step's own position, which is read
            # from the step itself: never under ``live``.
            read = block * sum(-(-position // block)
                               for position in positions) + len(active)
        else:
            read = self.max_batch * width * block
        return _Step(active, slots, rows, width, live, read,
                     unread is not None)

    def _read_unread(self) -> bool:
        """Read and emit the step in flight, leaving none."""
        step, self._unread = self._unread, None
        return self._read_step(step)

    def _read_step(self, step: _Step) -> bool:
        """Fetch ``step``'s tokens and emit them; False when the read
        failed (every request has failed with it)."""
        try:
            with tracing.phase("engine.decode.fetch"):
                out = self._fetch(np.asarray, step.out)
                # Let go of the device's copy HERE: freeing a device
                # array gives up the interpreter, and done at the end of
                # the tick that cost a pass 2 ms among 150 threads
                # (PR 36). A step launched on it holds its own reference.
                step.out = None
        except Exception as exc:  # noqa: BLE001 — donated pool is gone
            self._reset_after_failure(exc)
            return False
        # The step's own split of the key, kept once the step is known
        # to have run: a failed step leaves the key it was given.
        self._key = step.key
        self._pass.decoded = True
        active, width = step.active, step.width
        with tracing.phase("engine.decode.emit", rows=len(active)) as span:
            with _Held(self._lock, span):
                self._counters["host_calls"] += 2  # the call, the read
                self._counters["decode_steps"] += 1
                self._counters["decode_steps_ahead"] += step.ahead
                self._counters["launches_starved"] += step.starved
                if len(active) >= 2:
                    self._counters["batched_decode_steps"] += 1
                self._counters["block_rows"] += len(active)
                self._counters["kv_positions_live"] += step.live
                self._counters["kv_positions_read"] += step.read
                if width < self.blocks_per_seq:
                    self._counters["decode_steps_narrow"] += 1
                finished = whole = 0
                for slot, req in zip(step.slots, active):
                    self._counters["window_blocks_recycled"] += \
                        self._recycled(req.position,
                                       req.position + self._span)
                    if req.sealed or req not in self._sched.active:
                        continue  # expired/externally sealed mid-step
                    tokens, committed = self._family.advance(req, out[slot])
                    self._counters["decode_tokens"] += len(tokens)
                    self._counters["commit_rows"] += committed
                    whole += bool(tokens)
                    if self._deliver_locked(req, tokens):
                        self._finish_locked(req)
                        finished += 1
            # Annotated with the lock released: requests that ended, and
            # rows whose pass completed a block (its tokens went out).
            span.set(finished=finished, blocks=whole)
        return True

    def _maybe_chaos_slow_step(self) -> None:
        if chaos.ACTIVE is not None and chaos.ACTIVE.should(
                "llm.slow_step"):
            with self._lock:
                self._counters["slow_steps"] += 1
            delay = float(os.environ.get("RAY_TPU_LLM_SLOW_S", "2.0"))
            end = time.monotonic() + delay
            # Sliced sleep: a wedged step must still honor shutdown.
            while time.monotonic() < end \
                    and not self._shutdown.is_set():
                time.sleep(0.02)

    def _reset_after_failure(self, exc: Exception) -> None:
        """A failed jitted call invalidated the donated pool: fail
        every in-flight request typed and rebuild: the loop stays
        alive for the next request. A decode step in flight unread is
        dropped with them (its requests have failed), and ``_key`` stays
        the key of the last step that was read."""
        self._unread = None
        with self._lock:
            sched = self._sched
            victims = list(sched.waiting) + list(sched.active)
            if sched.prefilling is not None:
                victims.append(sched.prefilling)
            sched.waiting.clear()
            sched.active.clear()
            sched.prefilling = None
            for req in victims:
                sched.release(req)
        for req in victims:
            self._seal(req, exc)
        self._pool = self._new_pool()

    # ---------------------------------------------------------------- stats

    def engine_stats(self) -> dict:
        """Monotonic counters (ENGINE_STAT_KEYS — the heartbeat/
        /metrics payload)."""
        out = {key: int(self._counters.get(key, 0))
               for key in ENGINE_STAT_KEYS}
        out["blocks_allocated"] = int(self._sched.cache.blocks_allocated)
        out["blocks_freed"] = int(self._sched.cache.blocks_freed)
        out["process_cpu_us"] = time.process_time_ns() // 1000
        out.update(tracing.gc_counters())
        expert_stats = self._expert_stats
        if expert_stats is not None:
            # One transfer, here and nowhere else; it waits for the
            # step in flight on the caller's thread, not the engine's.
            out.update(read_stats(expert_stats))
        return out

    def engine_load(self) -> dict:
        """Live gauges (autoscaler feed; NOT counters — served through
        replica ``serve_metrics()``, not the counter family)."""
        with self._lock:
            return {
                "depth": self._sched.depth(),
                "waiting": len(self._sched.waiting),
                "active": len(self._sched.active),
                "free_blocks": self._sched.cache.free_blocks,
            }

    # ------------------------------------------------------------ lifecycle

    def check_health(self) -> None:
        if not self._loop_thread.is_alive() \
                and not self._shutdown.is_set():
            raise RuntimeError("LLM engine loop died")

    def shutdown(self) -> None:
        self._shutdown.set()
        with self._lock:
            self._lock.notify_all()
            sched = self._sched
            victims = list(sched.waiting) + list(sched.active)
            if sched.prefilling is not None:
                victims.append(sched.prefilling)
            sched.waiting.clear()
            sched.active.clear()
            sched.prefilling = None
            for req in victims:
                sched.release(req)
        for req in victims:
            self._seal(req, RuntimeError("LLM engine shut down"))
        self._loop_thread.join(timeout=5.0)
        tracing.forget_gc(self)

    def __del__(self):
        if hasattr(self, "_shutdown"):  # the constructor got that far
            self._shutdown.set()


# --------------------------------------------------------------------------
# Process-local registry (stats plumbing)
# --------------------------------------------------------------------------


def merged_engine_stats() -> "dict | None":
    """Summed ENGINE_STAT_KEYS across this process's live engines, or
    None when the process hosts none (heartbeats skip the group)."""
    engines = list(_LIVE)
    if not engines:
        return None
    out = {key: 0 for key in ENGINE_STAT_KEYS}
    for engine in engines:
        for key, value in engine.engine_stats().items():
            out[key] += int(value)
    # The process's, not an engine's: once, whatever the engines.
    out["process_cpu_us"] = time.process_time_ns() // 1000
    out.update(tracing.gc_counters())
    return out


def merged_engine_load() -> dict:
    totals = {"depth": 0, "waiting": 0, "active": 0, "free_blocks": 0}
    for engine in list(_LIVE):
        for key, value in engine.engine_load().items():
            totals[key] += int(value)
    return totals
