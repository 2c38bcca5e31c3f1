"""The hybrid family (``models/phi4flash.py`` over the three caches of
``serve/llm_engine/hybrid.py``) against the plain float32 reference
``benchmark/reference/phi4flash_decoder.py``, tiny and seeded: logits,
not tokens. A window of 8, blocks of 4 and chunks of 8 make a ring of 20
positions a row, which every longer context wraps."""

import functools
import threading
import time

import numpy as np
import prefill_chunk_cases
import pytest

BLOCK, CHUNK, ROWS, TABLE = 4, 8, 4, 16      # 64 positions a row
RING = 20


def tiny(**changes):
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash as phi

    return phi.Phi4FlashConfig.tiny(**{"dtype": jnp.float32, "max_seq_len": 64,
                                       **changes})


def numbers(cfg) -> dict:
    """The configuration file's Hugging Face numbers, as the reference
    is given them."""
    return {"hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "num_hidden_layers": cfg.num_layers,
            "sliding_window": cfg.sliding_window,
            "layer_norm_eps": cfg.layer_norm_eps}


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """(weights, the chunk forward showing every position's logits, the
    decode forward), the cache donated as the engine's programs donate
    it."""
    import jax

    from ray_tpu.serve.llm_engine import hybrid, model

    params = model.serving_params(cfg, None, seed=11)
    chunk = jax.jit(
        lambda params, cache, tokens, positions, table, slot, n_valid:
        hybrid.forward(params, cache, tokens, positions, table, cfg, BLOCK,
                       slot=slot, n_valid=n_valid)[:2], donate_argnums=(1,))
    step = jax.jit(
        lambda params, cache, tokens, positions, tables:
        hybrid.forward(params, cache, tokens, positions[:, None], tables,
                       cfg, BLOCK)[:2], donate_argnums=(1,))
    return params, chunk, step


def fresh_cache(cfg):
    from ray_tpu.serve.llm_engine import hybrid

    return hybrid.init_cache(cfg, 1 + ROWS * TABLE, BLOCK, ROWS, CHUNK)


def reference_logits(cfg, params, context):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import phi4flash_decoder as reference

    return np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, numbers(cfg)))(
            params, jnp.asarray(context)[None]))[0]


def contexts_of(lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n) for n in lengths]


def serve(cfg, contexts, prefilled, slots, cache=None, tables=None):
    """Each context's first ``prefilled`` positions through prefill
    chunks, the rest through batched decode steps, as the engine drives
    its two programs: row slot ``slots[i]``, a table of its own. Returns
    (every position's logits per context, the cache)."""
    import jax.numpy as jnp

    params, chunk, step = programs(cfg)
    cache = fresh_cache(cfg) if cache is None else cache
    if tables is None:
        tables = np.zeros((ROWS, TABLE), np.int32)
        for slot in slots:
            tables[slot] = 1 + slot * TABLE + np.arange(TABLE)
    got = [np.zeros((len(c), cfg.vocab_size), np.float32) for c in contexts]
    for i, context in enumerate(contexts):
        for start in range(0, prefilled[i], CHUNK):
            n = min(CHUNK, prefilled[i] - start)
            tokens = np.zeros((1, CHUNK), np.int32)
            positions = np.zeros((1, CHUNK), np.int32)
            tokens[0, :n] = context[start:start + n]
            positions[0, :n] = np.arange(start, start + n)
            logits, cache = chunk(
                params, cache, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(tables[slots[i]][None]), np.int32(slots[i]),
                np.int32(n))
            got[i][start:start + n] = np.asarray(logits[0, :n])
    at = list(prefilled)
    while any(at[i] < len(c) for i, c in enumerate(contexts)):
        tokens = np.zeros((ROWS, 1), np.int32)
        positions = np.zeros((ROWS,), np.int32)
        step_tables = np.zeros((ROWS, TABLE), np.int32)
        active = [i for i, c in enumerate(contexts) if at[i] < len(c)]
        for i in active:
            tokens[slots[i], 0], positions[slots[i]] = contexts[i][at[i]], at[i]
            step_tables[slots[i]] = tables[slots[i]]
        logits, cache = step(params, cache, jnp.asarray(tokens),
                             jnp.asarray(positions),
                             jnp.asarray(step_tables))
        logits = np.asarray(logits[:, 0])
        for i in active:
            got[i][at[i]] = logits[slots[i]]
            at[i] += 1
    return got, cache


def worst_over_std(got, want) -> float:
    return float(np.abs(got - want).max() / want.std())


# Ragged rows: inside one chunk and block; across a block and a chunk;
# past the window (8); past the ring (20), prefilled across its wrap;
# decoded across the wrap.
RAGGED = [(3, 12), (9, 9), (27, 14), (11, 30)]
F32_TOLERANCE = 2e-4  # float32 against float32, in standard deviations


def test_paged_logits_match_the_reference_for_ragged_rows():
    cfg = tiny()
    contexts = contexts_of([p + d for p, d in RAGGED])
    got, cache = serve(cfg, contexts, [p for p, _ in RAGGED], [2, 0, 3, 1])
    for context, logits in zip(contexts, got):
        want = reference_logits(cfg, programs(cfg)[0], context)
        assert worst_over_std(logits, want) < F32_TOLERANCE, len(context)
    assert cache["win_k"].shape[2] == RING
    assert str(cache["ssm"].dtype) == "float32"


def test_bfloat16_programs_stay_near_the_float32_reference():
    """The dtype the configuration serves in: bf16 weights and
    activations, float32 state. At this size (64 wide, where a bf16
    rounding is a larger share of a sum than at 2560) its logits stay
    within 0.3 standard deviations of the float32 reference on the same
    (bf16-valued) weights; the controls below are held to the float32
    programs' far tighter tolerance."""
    import jax.numpy as jnp

    cfg = tiny(dtype=jnp.bfloat16)
    contexts = contexts_of([p + d for p, d in RAGGED])
    got, _ = serve(cfg, contexts, [p for p, _ in RAGGED], [0, 1, 2, 3])
    worst = max(worst_over_std(
        logits, reference_logits(cfg, programs(cfg)[0], context))
        for context, logits in zip(contexts, got))
    assert worst < 0.3, worst


@pytest.mark.parametrize("control, change", [
    ("state-in-bfloat16", {"state_dtype": "bfloat16"}),
    ("window-off-by-one", {"window_shift": 1}),
    ("lambda-dropped", {"drop_lambda": True}),
])
def test_a_control_fails_the_comparison(control, change):
    """What must NOT pass: the recurrent state kept in bfloat16, the
    window one position too wide, differential attention without its
    lambda. Each is far outside the tolerance the sound program meets."""
    import jax.numpy as jnp

    if "state_dtype" in change:
        change = {"state_dtype": jnp.bfloat16}
    cfg = tiny(**change)
    contexts = contexts_of([p + d for p, d in RAGGED])
    got, _ = serve(cfg, contexts, [p for p, _ in RAGGED], [0, 1, 2, 3])
    sound = tiny()
    worst = max(worst_over_std(
        logits, reference_logits(sound, programs(cfg)[0], context))
        for context, logits in zip(contexts, got))
    assert worst > 10 * F32_TOLERANCE, (control, worst)


def test_a_row_alone_and_among_batchmates_gives_the_same_logits():
    cfg = tiny()
    contexts = contexts_of([p + d for p, d in RAGGED])
    prefilled = [p for p, _ in RAGGED]
    together, _ = serve(cfg, contexts, prefilled, [2, 0, 3, 1])
    for i, slot in enumerate([2, 0, 3, 1]):
        alone, _ = serve(cfg, contexts[i:i + 1], prefilled[i:i + 1], [slot])
        np.testing.assert_allclose(alone[0], together[i], atol=2e-5)


@pytest.mark.parametrize("case", ["next-tenant", "preempted-and-resumed"])
def test_a_used_slot_gives_the_logits_of_a_fresh_run(case):
    """A slot's state and ring are never cleared on the host: the chunk
    at position 0 starts from zeros in the program, and a ring entry the
    request has not written is masked. The next tenant of a slot, and a
    request that is preempted (its blocks freed, the slot and its stale
    state kept or handed on) and recomputed from position 0, read
    nothing of what was there."""
    cfg = tiny()
    first, second = contexts_of([41, 33], seed=9)
    fresh, _ = serve(cfg, [second], [25], [1])
    # The slot's last tenant fills the ring and the state and wraps.
    _, cache = serve(cfg, [first], [30], [1])
    if case == "preempted-and-resumed":
        # The request itself, stopped part-way through its decode.
        _, cache = serve(cfg, [second[:29]], [25], [1], cache=cache)
    again, _ = serve(cfg, [second], [25], [1], cache=cache)
    np.testing.assert_allclose(again[0], fresh[0], atol=2e-5)


def test_padding_and_inactive_rows_advance_no_state():
    """A decode step in which a row is inactive, and a prefill chunk's
    padding, leave that row's state and ring as they were."""
    cfg = tiny()
    context, other = contexts_of([13, 40], seed=3)
    _, cache = serve(cfg, [context], [13], [2])      # 8 + 5: padded chunk
    before = {k: np.asarray(v) for k, v in cache.items()}
    _, cache = serve(cfg, [other], [20], [0], cache=cache)  # slot 2 idle
    for name in ("ssm", "conv", "win_k", "win_v"):
        np.testing.assert_array_equal(np.asarray(cache[name])[:, 2],
                                      before[name][:, 2])
    # And the padded chunk's state is the unpadded computation's: one
    # more token decoded from it matches the reference.
    longer = np.concatenate([context, [7]])
    got, _ = serve(cfg, [longer], [13], [2])
    want = reference_logits(cfg, programs(cfg)[0], longer)
    assert worst_over_std(got[0], want) < F32_TOLERANCE


# --------------------------------------------- mixers, form against form


def mixer_inputs(cfg, length=27, seed=2):
    import jax

    from ray_tpu.models import phi4flash as phi

    params = phi.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    h = rng.standard_normal((length, cfg.hidden_size)).astype(np.float32)
    return params, h


def in_chunks(length):
    return [(s, min(CHUNK, length - s)) for s in range(0, length, CHUNK)]


def padded(h, start, n):
    out = np.zeros((CHUNK, h.shape[1]), np.float32)
    out[:n] = h[start:start + n]
    return out


def steps_for(cfg, cache, start, n, form):
    """``hybrid``'s view of one row in slot 1: a chunk at ``start``, or
    the decode step of position ``start``."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm_engine import hybrid

    table = 1 + TABLE + jnp.arange(TABLE, dtype=jnp.int32)
    if form == "chunk":
        positions = jnp.where(jnp.arange(CHUNK) < n,
                              start + jnp.arange(CHUNK), 0)[None]
        return hybrid._ChunkSteps(
            cfg, BLOCK, cache, positions, table[None],
            jnp.arange(CHUNK)[None] < n, slot=1, n_valid=n)
    positions = jnp.zeros((ROWS, 1), jnp.int32).at[1, 0].set(start)
    tables = jnp.zeros((ROWS, TABLE), jnp.int32).at[1].set(table)
    # Position 0 is served by a chunk in the engine; here the row is
    # marked active by hand.
    return hybrid._DecodeSteps(cfg, BLOCK, cache, positions, tables,
                               jnp.zeros((ROWS, 1), bool).at[1, 0].set(True))


def run_mixer(cfg, mixer, form, params, h):
    """One mixer over the positions of ``h`` through the caches, a
    chunk at a time or a token at a time. Returns [L, E]."""
    import jax.numpy as jnp

    length = len(h)
    cache = fresh_cache(cfg)
    out = np.zeros_like(h)
    w_ssm, w_attn = params["mid_ssm"]["ssm"], params["mid_attn"]["attn"]
    w_back = {k: v[0] for k, v in params["back"]["cross"].items()}
    # The cross layer reads what the full layer wrote: fill that first.
    spans = in_chunks(length) if form == "chunk" \
        else [(p, 1) for p in range(length)]
    for start, n in spans:
        steps = steps_for(cfg, cache, start, n, form)
        if form == "chunk":
            x = jnp.asarray(padded(h, start, n))[None]
        else:
            x = jnp.zeros((ROWS, 1, h.shape[1])).at[1, 0].set(h[start])
        if mixer == "ssm":
            # A fresh row: zero state (the chunk form zeroes at 0 itself).
            got, memory, ssm, conv = steps.ssm(w_ssm, x, cache["ssm"],
                                               cache["conv"], 0)
            cache = {**cache, "ssm": ssm, "conv": conv}
        elif mixer == "window":
            got, win_k, win_v = steps.window(w_attn, 5, x, cache["win_k"],
                                             cache["win_v"], 1)
            cache = {**cache, "win_k": win_k, "win_v": win_v}
        else:
            got, pool_k, pool_v, keys, values = steps.full(
                w_attn, 5, x, cache["k"], cache["v"])
            cache = {**cache, "k": pool_k, "v": pool_v}
            if mixer == "cross":
                got = steps.cross(w_back, 7, x, keys, values)
        got = np.asarray(got)
        out[start:start + n] = got[0, :n] if form == "chunk" else got[1]
    return out


def reference_mixer(cfg, mixer, params, h):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import phi4flash_decoder as reference

    eps, x = cfg.layer_norm_eps, jnp.asarray(h)[None]
    with jax.default_matmul_precision("highest"):
        if mixer == "ssm":
            return np.asarray(reference.state_space(
                params["mid_ssm"]["ssm"], x)[0])[0]
        w = params["mid_attn"]["attn"]
        e, kv = cfg.hidden_size, cfg.num_kv_heads * cfg.head_dim
        qkv = x @ w["wqkv"] + w["bqkv"]
        q, k, v = (reference.split_heads(qkv[..., :e], cfg.num_heads),
                   reference.split_heads(qkv[..., e:e + kv],
                                         cfg.num_kv_heads),
                   reference.split_heads(qkv[..., e + kv:], cfg.num_kv_heads))
        if mixer == "cross":
            w = {name: a[0] for name, a in params["back"]["cross"].items()}
            q = reference.split_heads(x @ w["wq"] + w["bq"], cfg.num_heads)
            return np.asarray(reference.differential_attention(
                w, 7.0, q, k, v, None, eps))[0]
        window = cfg.sliding_window if mixer == "window" else None
        return np.asarray(reference.differential_attention(
            w, 5.0, q, k, v, window, eps))[0]


@pytest.mark.parametrize("mixer", ["ssm", "window", "full", "cross"])
def test_a_mixers_chunk_form_is_its_token_form_is_the_reference(mixer):
    cfg = tiny()
    params, h = mixer_inputs(cfg)
    want = reference_mixer(cfg, mixer, params, h)
    for form in ("chunk", "token"):
        got = run_mixer(cfg, mixer, form, params, h)
        np.testing.assert_allclose(got, want, atol=3e-5 * (1 + np.abs(want).max()),
                                   err_msg=form)


def test_the_gated_memory_unit_is_the_reference():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash as phi

    cfg = tiny()
    params, h = mixer_inputs(cfg)
    w = {k: v[0] for k, v in params["back"]["gmu"].items()}
    memory = np.random.default_rng(1).standard_normal(
        (len(h), cfg.d_inner)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = (jax.nn.silu(h @ w["w1"]) * memory) @ w["w2"]
    np.testing.assert_allclose(
        np.asarray(phi.gmu(w, jnp.asarray(h), jnp.asarray(memory), cfg)),
        np.asarray(want), atol=1e-4)


# ------------------------------------------------------ the caches' sizes


@pytest.mark.parametrize("context", [4096, 32768, 262144])
def test_the_window_layers_hold_560_positions_a_row_at_any_context(context):
    """At the published widths and the program's defaults (blocks of
    16, chunks of 32): window + chunk + block, whatever the context; ONE
    layer of full-attention pool; a float32 state."""
    import jax

    from ray_tpu.models import phi4flash as phi
    from ray_tpu.serve.llm_engine import hybrid

    cfg = phi.Phi4FlashConfig(max_seq_len=context)
    rows, blocks = 32, 1 + 32 * context // 16
    cache = jax.eval_shape(
        lambda: hybrid.init_cache(cfg, blocks, 16, rows, 32))
    assert hybrid.ring_positions(cfg, 16, 32) == 512 + 32 + 16 == 560
    assert cache["win_k"].shape == cache["win_v"].shape == \
        (8, rows, 560, 1280)
    assert cache["k"].shape == cache["v"].shape == (1, blocks, 16, 1280)
    assert cache["ssm"].shape == (9, rows, 5120, 16)
    assert str(cache["ssm"].dtype) == "float32"
    assert cache["conv"].shape == (9, rows, 3, 5120)
    per_row = {name: a.size * a.dtype.itemsize // rows
               for name, a in cache.items() if name not in ("k", "v")}
    assert per_row["win_k"] + per_row["win_v"] == 8 * 560 * 5120
    assert 2 * cfg.num_kv_heads * cfg.head_dim * 2 == 5120  # bytes, bf16


def test_the_published_sizes_count_as_the_issue_counts_them():
    from ray_tpu.models import phi4flash as phi

    cfg = phi.Phi4FlashConfig()
    assert (cfg.head_dim, cfg.d_inner, cfg.dt_rank) == (64, 5120, 160)
    assert (cfg.ssm_layers, cfg.window_layers, cfg.back_periods) == (9, 8, 7)
    assert cfg.num_params == 3_852_562_944
    assert round(cfg.num_params * 2 / 2 ** 30, 2) == 7.18
    import jax

    shapes = jax.eval_shape(
        lambda: phi.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == cfg.num_params
    with pytest.raises(ValueError, match="multiple of 4"):
        phi.Phi4FlashConfig(num_layers=6)


def test_large_tensors_are_drawn_a_block_of_rows_at_a_time(monkeypatch):
    """With ``DRAW`` set below the tiny model's matrices (at the
    published widths it is below every matrix) a tensor is drawn in
    blocks of whole rows: the same tree, the seed's weights again on a
    second call, no block drawn twice, the moments a single draw has,
    and the dtype asked for; at or under ``DRAW`` the draw is the plain
    one, value for value."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash as phi

    cfg, key = tiny(), jax.random.PRNGKey(7)
    whole = phi.init_params(cfg, key)
    monkeypatch.setattr(phi, "DRAW", 96)  # cols 64..256: 1 row a block
    blocked = phi.init_params(cfg, key)
    again = phi.init_params(cfg, key)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), whole) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), blocked)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(blocked), jax.tree.leaves(again)))
    w1, plain = blocked["front"]["block_a"]["w1"], \
        whole["front"]["block_a"]["w1"]
    assert w1.size > 96 and not bool((w1 == plain).all())
    rows = np.asarray(w1).reshape(-1, w1.shape[-1])
    assert len({row.tobytes() for row in rows}) == len(rows)
    assert abs(float(w1.std()) / float(plain.std()) - 1) < 0.05
    assert abs(float(w1.mean())) < 0.01
    small = blocked["final_norm"]["bias"]  # 64 elements: one draw
    assert bool((small == whole["final_norm"]["bias"]).all())
    held = phi.scaled_normal(key, (6, 8, 32), 0.5, jnp.bfloat16)
    assert held.dtype == jnp.bfloat16 and held.shape == (6, 8, 32)
    assert abs(float(held.astype(jnp.float32).std()) - 0.5) < 0.05


# ------------------------------------------------------- through the engine


@pytest.fixture(scope="module")
def engine():
    from ray_tpu.serve.llm_engine import LLMEngine

    engine = LLMEngine(tiny(), max_batch_size=ROWS, max_seq_len=64,
                       block_size=BLOCK, prefill_chunk=CHUNK, seed=11)
    yield engine
    engine.shutdown()


def greedy_by_reference(cfg, params, prompt, new_tokens):
    """The float32 reference's own greedy continuation."""
    context = list(prompt)
    for _ in range(new_tokens):
        context.append(int(reference_logits(
            cfg, params, np.asarray(context))[-1].argmax()))
    return context[len(prompt):]


def test_the_engine_serves_the_references_greedy_tokens(engine):
    """The normal path: ``LLMEngine`` with the same scheduler and
    allocator as a dense model, ragged requests batched, contexts past
    the ring."""
    prompts = contexts_of([5, 13, 26], seed=4)
    requests = [engine.submit(p.tolist(), max_new_tokens=8) for p in prompts]
    for prompt, request in zip(prompts, requests):
        got = engine.result(request, timeout_s=300)
        assert got == greedy_by_reference(engine.config, engine.params,
                                          prompt.tolist(), 8)
    stats = engine.engine_stats()
    assert stats["state_resets"] == stats["admitted"]
    # Contexts of at most 26 + 8 positions: the table's 64 are read only
    # while the longest row is past 32 (``engine.table_widths``).
    narrow = stats["decode_steps_narrow"]
    assert 0 < narrow < stats["decode_steps"]
    assert stats["decode_steps"] * ROWS * 16 < stats["kv_positions_read"] \
        <= ROWS * (32 * narrow + 64 * (stats["decode_steps"] - narrow))
    assert 0 < stats["kv_positions_live"] < stats["kv_positions_read"]
    # 26 + 8 positions pass the ring's 20: blocks 5.. are written over.
    assert stats["window_blocks_recycled"] >= 3


def test_preempted_requests_resume_to_the_same_tokens(engine):
    """Cache pressure preempts and recomputes: the state restarts from
    zero at the resumed request's first chunk, and every request's
    greedy tokens are those of the run without pressure."""
    from ray_tpu.serve.llm_engine import LLMEngine

    prompts = [p.tolist() for p in contexts_of([3, 5, 2, 4], seed=6)]
    want = [engine.result(engine.submit(p, max_new_tokens=12), timeout_s=300)
            for p in prompts]
    pressed = LLMEngine(engine.config, engine.params, max_batch_size=ROWS,
                        max_seq_len=64, block_size=BLOCK,
                        prefill_chunk=CHUNK, num_blocks=11, seed=11)
    try:
        results, lock = {}, threading.Lock()

        def generate(i):
            out = pressed.result(pressed.submit(prompts[i],
                                                max_new_tokens=12),
                                 timeout_s=300)
            with lock:
                results[i] = out

        threads = [threading.Thread(target=generate, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = pressed.engine_stats()
        assert stats["preemptions"] > 0 and stats["resumes"] > 0, stats
        assert stats["state_resets"] == 4 + stats["resumes"]
        assert [results[i] for i in range(4)] == want
    finally:
        pressed.shutdown()


def test_a_row_dropped_with_its_token_in_flight_leaves_a_clean_slot(engine):
    """Steps run one ahead of the host's reads. A request sealed by its
    caller while the step that advances its state is unread: the step
    already queued still writes its ring and its state, before the
    slot's next tenant's first chunk, which starts from zero: the
    tenant, in the same slot, and the batchmate get the reference's
    greedy tokens."""
    cfg, params = engine.config, engine.params
    mate, tenant = (p.tolist() for p in contexts_of([7, 11], seed=8))
    before = engine.engine_stats()
    doomed = engine.submit(contexts_of([9], seed=3)[0].tolist(),
                           max_new_tokens=40)
    batchmate = engine.submit(mate, max_new_tokens=30)
    for _ in range(100_000):
        with engine._lock:
            unread = engine._unread
            if unread is not None and doomed in unread.active \
                    and len(doomed.output) >= 4:
                slot = doomed.slot
                assert engine._seal(doomed, RuntimeError("caller left"))
                break
        time.sleep(0.0005)
    else:
        pytest.fail("never saw the request in a step in flight")
    with pytest.raises(RuntimeError, match="caller left"):
        engine.result(doomed, timeout_s=300)
    while doomed.slot >= 0:  # the sweep gives the slot back
        time.sleep(0.0005)
    next_tenant = engine.submit(tenant, max_new_tokens=10)
    while next_tenant.slot < 0 and not next_tenant.done.is_set():
        time.sleep(0.0005)
    assert next_tenant.slot in (slot, -1)  # the same row, while it runs
    assert engine.result(next_tenant, timeout_s=300) == \
        greedy_by_reference(cfg, params, tenant, 10)
    assert engine.result(batchmate, timeout_s=300) == \
        greedy_by_reference(cfg, params, mate, 30)
    after = engine.engine_stats()
    steps = after["decode_steps"] - before["decode_steps"]
    ahead = after["decode_steps_ahead"] - before["decode_steps_ahead"]
    assert 0 < steps - 4 <= ahead < steps
    assert after["state_resets"] - before["state_resets"] == 3


def test_row_slots_are_given_back():
    from ray_tpu.serve.llm_engine import PagedKVCache
    from ray_tpu.serve.llm_engine.scheduler import EngineRequest, Scheduler

    sched = Scheduler(PagedKVCache(9, 4, 8), max_batch=2, max_waiting=8,
                      max_tokens_per_seq=32)
    first, second, third = (EngineRequest([1, 2], 4, 0.0) for _ in range(3))
    for req in (first, second, third):
        sched.try_enqueue(req)
    assert sched.claim_prefill() is first and first.slot == 0
    sched.prefilling = None
    sched.active.append(first)
    assert sched.claim_prefill() is second and second.slot == 1
    sched.preempt(second)
    assert second.slot == -1 and sched.waiting[0] is second
    assert sched.claim_prefill() is second and second.slot == 1
    sched.release(first)
    sched.active.remove(first)
    sched.prefilling = None
    sched.active.append(second)
    assert sched.claim_prefill() is third and third.slot == 0


@pytest.mark.parametrize("chunk", prefill_chunk_cases.WIDTHS,
                         ids=prefill_chunk_cases.WIDTH_IDS)
def test_greedy_tokens_do_not_depend_on_the_chunk_width(chunk):
    """The ring is the window, a chunk and a block, so a chunk wider
    than the window (8 positions here, the default's 128 in a chunk)
    still reads the window before its first token; the state-space scan
    carries through a chunk as it does through sixteen of them."""
    prefill_chunk_cases.same_tokens_at(tiny(), chunk)


def test_a_preemption_inside_a_wide_chunks_prompt_resumes_exact():
    """A preempted hybrid request re-prefills from position 0, where
    its first chunk resets the state its row slot held."""
    prefill_chunk_cases.resumes_to_the_same_tokens(tiny())


def test_the_family_is_looked_up_in_one_place():
    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import hybrid, model

    assert model.family(tiny()) is hybrid.FAMILY
    assert model.family(llama.LlamaConfig.tiny()) is model.PAGED
    assert model.PAGED.ring_positions(None, 16, 32) == 0
    assert hybrid.FAMILY.recurrent and not model.PAGED.recurrent
    rows = model.pack_decode_rows(4, 3, [(7, 5, 0.5, [2, 9])], [2])
    assert rows[2, :2].tolist() == [7, 5] and rows[2, 3:5].tolist() == [2, 9]
    assert not rows[[0, 1, 3]].any()
    chunk = hybrid.FAMILY.pack_prefill_chunk(4, 3, [5, 6], 8, [1, 2], slot=3)
    assert chunk.tolist() == [2, 1, 3, 5, 6, 0, 0, 8, 9, 0, 0, 1, 2, 0]
