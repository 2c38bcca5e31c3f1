"""``LLMEngineServer``: the serve deployment hosting one paged engine.

Request (token-in/token-out, no tokenizer dependency)::

    {"tokens": [int], "max_new_tokens": int, "temperature": float}
      -> {"tokens": [int]}               (__call__, unary)
    and, for a model that generates by diffusion over blocks, optionally
    "denoising_steps": int, "remasking": "sequential" |
    "low_confidence_static" | "low_confidence_dynamic",
    "confidence_threshold": float (left out: the model configuration's)
    generate(request)  -> yields int tokens  (streaming: run with
      handle.options(stream=True).generate.remote(...) and TTFT is
      the first chunk's arrival)

Deadline inheritance: the serve tier's per-request budget
(``HTTPOptions.request_timeout_s`` / ``handle.options(deadline_s=)``)
rides the actor call (PR 7) and is read back here via
``get_runtime_context().get_task_deadline()`` — the engine's internal
queue refuses dead work typed (``TaskTimeoutError`` stage
``llm_queue``/``llm_decode``) instead of decoding tokens nobody is
waiting for. A full waiting queue or unservable request sheds
``CacheExhaustedError`` through the ``SystemOverloadedError`` path
(HTTP 503 + Retry-After).
"""

from __future__ import annotations

from ray_tpu.serve import replica
from ray_tpu.serve.llm_engine.engine import LLMEngine


class LLMEngineServer:
    """Deployment class: ``serve.run(serve.deployment(LLMEngineServer)
    .bind(config, params, ...))``."""

    def __init__(self, config=None, params: "dict | None" = None, *,
                 max_batch_size: int = 8,
                 max_seq_len: "int | None" = None,
                 block_size: "int | None" = None,
                 num_blocks: "int | None" = None,
                 prefill_chunk: "int | None" = None,
                 max_waiting: "int | None" = None,
                 seed: int = 0, mesh=None):
        self._engine = LLMEngine(
            config, params, max_batch_size=max_batch_size,
            max_seq_len=max_seq_len, block_size=block_size,
            num_blocks=num_blocks, prefill_chunk=prefill_chunk,
            max_waiting=max_waiting, seed=seed, mesh=mesh)

    # ------------------------------------------------------------ data path

    @staticmethod
    def _deadline(request: dict) -> "float | None":
        """Explicit per-request budget wins; otherwise inherit the
        serve call's PR-7 deadline from the runtime context."""
        import time

        deadline_s = request.get("deadline_s")
        if deadline_s is not None:
            return time.time() + float(deadline_s)
        from ray_tpu.runtime_context import get_runtime_context

        return get_runtime_context().get_task_deadline()

    def _submit(self, request: dict, stream: bool = False):
        """The engine's request carries the streamed request's id (None
        for a unary call), and the replica's admission span ends where
        the engine's queue is reached."""
        req = self._engine.submit(
            list(request.get("tokens") or []),
            max_new_tokens=int(request.get("max_new_tokens", 16)),
            temperature=float(request.get("temperature", 0.0)),
            deadline=self._deadline(request), stream=stream,
            denoising_steps=request.get("denoising_steps"),
            remasking=request.get("remasking"),
            confidence_threshold=request.get("confidence_threshold"),
            request_id=replica.current_stream_request_id())
        replica.stream_request_admitted()
        return req

    def __call__(self, request: dict) -> dict:
        return {"tokens": self._engine.result(self._submit(request),
                                              timeout_s=120.0)}

    def generate(self, request: dict):
        """Streaming generation — tokens yield as decode steps emit
        them (pair with ``handle.options(stream=True)``)."""
        yield from self._engine.stream_tokens(self._submit(request, True))

    def generate_batches(self, request: dict):
        """``generate`` as a replica streams it: lists of the tokens that
        were waiting, so a stream that has fallen behind the engine is
        delivered a call at a time (``Replica.handle_request_streaming``
        looks for this sibling); a caller of the handle sees single
        tokens either way."""
        yield from self._engine.stream_token_batches(
            self._submit(request, True))

    # --------------------------------------------------------- control path

    def engine_stats(self) -> dict:
        """ENGINE_STAT_KEYS counters (the benchmark and tests read
        this through the deployment handle)."""
        return self._engine.engine_stats()

    def serve_metrics(self) -> dict:
        """Live load gauges merged into ``Replica.get_metrics()`` —
        the engine-depth signal the latency autoscaler folds in."""
        load = self._engine.engine_load()
        return {"engine_depth": load["depth"],
                "engine_free_blocks": load["free_blocks"]}

    def check_health(self) -> None:
        self._engine.check_health()

    def __del__(self):
        try:
            self._engine.shutdown()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
