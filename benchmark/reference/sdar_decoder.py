"""Plain reference of SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat): a Qwen3-MoE decoder
layer under a mask by blocks, and generation by diffusion over blocks.
Written from memory of the published ``modeling_sdar_moe.py`` and
``generate.py``: there is no network here. For a layer with input ``x``:

    n = RMSNorm(x)
    q = n Wq (32 heads of 128),  k = n Wk,  v = n Wv (4 heads)   no bias
    q = RMSNorm(q; scale [128]),  k = RMSNorm(k; scale [128])   over EACH
                                 head's 128 values, one scale for all
                                 query heads and one for all key heads
    rotary embedding on halves (rotate_half, theta) on q and k
    position i sees position j where j // block_length <= i // block_length:
        all of its own block, both ways, and every earlier block
    h = x + (softmax attention at scale head_dim^-0.5) Wo
    m = RMSNorm(h)
    p = softmax(m Wr) over ALL 128 experts, float32
    the 8 largest p with their indices, divided by their sum
    y = h + sum_k p_k * Wdown_k(silu(Wgate_k m) * Wup_k m)    width 768

Then a final RMSNorm and an untied output head. THE LOGITS AT POSITION
``p`` ARE FOR THE TOKEN AT ``p``: a masked position is filled in place,
there is no shift by one.

Generation (``generate``): the prompt's whole blocks are context; what
is left of it opens the first generated block as known positions. A
block starts as its known tokens and ``mask_token_id`` elsewhere. A
denoising pass runs the model on the context and the block, takes at
every masked position the argmax ``x0`` and its probability ``c``, and
fixes ``n_t`` of them (``fix_count``: ``block_length // steps``, the
remainder on the first passes): ``sequential`` the leftmost,
``low_confidence_static`` the largest ``c``, ``low_confidence_dynamic``
every one with ``c`` over the threshold, and the ``n_t`` largest if
fewer pass it. When none is masked the next block starts; the answer is
cut at ``max_new_tokens``. There is no cache here, so the published
code's last pass of a block, which only stores its keys and values, has
nothing to do: every pass is a whole forward over the context and the
block.

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks. It calls nothing of ``ray_tpu``; it shares only the
layout of the parameter tree (``embed.tokens [V, E]``,
``layers.{attn_norm, wq [n, E, H, D], wk, wv [n, E, KV, D], q_norm,
k_norm [n, D], wo [n, H, D, E], mlp_norm, w_router [n, E, X], w_gate,
w_up [n, X, E, M], w_down [n, X, M, E]}``, ``final_norm``, ``lm_head
[E, V]``). ``model`` is the configuration file's dictionary of Hugging
Face keys with ``block_length``, ``denoising_steps`` and
``mask_token_id`` beside them (the harness hands over the file's
numbers only, so ``norm_topk_prob``, a boolean, is read as true where it
is left out: it is what this model publishes).

Departures from the published code, each for a reason:

- the mask's id is never a token: it is left out of the argmax and of
  the softmax that gives ``c`` (with random weights an argmax can be
  the mask's id, and a block would then never be done; which positions
  are fixed is bookkeeping, not a comparison of tokens with the mask).
  ``forward`` sets its logit to the row's least, so that it decides
  nothing there either;
- greedy only: a draw at a temperature cannot be compared;
- for memory only: an expert is widened to float32 when it is applied
  (a ``scan`` over the experts), and the head a slice of the vocabulary
  at a time; the arithmetic is unchanged.

``forward(params, tokens, model)`` is what the benchmark's harness
calls with the prompt and the served tokens of a row: entry ``p - 1``
of its result holds the logits that DECIDED position ``p`` under the
``sequential`` rule and ``model``'s ``denoising_steps``, where the order
in which positions are fixed follows from the schedule alone (under the
other rules it follows from the confidences, which the served tokens do
not show). Every block of the row is replayed: the pass that fixes
``p`` sees the row's own tokens at the block's positions fixed by
earlier passes, the mask at the others, and every earlier block clean.
All blocks and passes are computed at once, as streams through the
layers: the clean tokens (block ``b`` over clean blocks ``<= b``), and
for each pass the noised tokens (block ``b`` over clean blocks ``< b``
and over its own noised self). It takes every block for fully masked
at its start: a prompt's remainder is not known to it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")
HEAD_SLICES = 8  # the head is applied to an eighth of the vocabulary a time


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, theta):
    """x: [.., L, H, D] at positions 0..L-1; position l rotates the pair
    (x[i], x[i + D/2]) by the angle l * theta^(-2i/D)."""
    length, half = x.shape[-3], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(length, dtype=F32)[:, None] * freqs     # [L, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, allowed):
    """Softmax attention of q [B, Lq, H, D] over k, v [B, Lk, KV, D]
    where ``allowed`` [Lq, Lk]; query head h reads key-value head
    h // (H / KV)."""
    h, kv, d = q.shape[2], k.shape[2], q.shape[3]
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    scores = jnp.where(allowed, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def experts(m, w, idx, weights):
    """sum over a token's chosen experts of weight * expert(m); expert
    ``e`` is applied to every token and kept where the token chose it."""
    def one_expert(total, expert):
        e, w_gate, w_up, w_down = expert
        out = (jax.nn.silu(m @ w_gate.astype(F32)) * (m @ w_up.astype(F32))) \
            @ w_down.astype(F32)
        chose = idx == e
        weight = jnp.sum(jnp.where(chose, weights, 0.0), axis=-1)
        return total + jnp.where(jnp.any(chose, axis=-1)[..., None],
                                 weight[..., None] * out, 0.0), None

    count = w["w_gate"].shape[0]
    total, _ = lax.scan(one_expert, jnp.zeros_like(m),
                        (jnp.arange(count), w["w_gate"], w["w_up"],
                         w["w_down"]))
    return total


def layer(streams, w, model):
    """One decoder layer for ``streams`` [1 + S, B, L, E]: stream 0 the
    clean tokens, the others noised copies (S may be 0). Returns the
    streams' outputs and the chosen experts [1 + S, B, L, k], sorted."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    size, length = model["block_length"], streams.shape[2]
    small = {k: v.astype(F32) for k, v in w.items()
             if k not in ("w_gate", "w_up", "w_down")}
    n = rms_norm(streams, small["attn_norm"], eps)
    q = rope(rms_norm(jnp.einsum("sble,ehd->sblhd", n, small["wq"]),
                      small["q_norm"], eps), theta)
    k = rope(rms_norm(jnp.einsum("sble,ekd->sblkd", n, small["wk"]),
                      small["k_norm"], eps), theta)
    v = jnp.einsum("sble,ekd->sblkd", n, small["wv"])
    block = jnp.arange(length) // size
    earlier = block[None, :] < block[:, None]                   # [Lq, Lk]
    same = block[None, :] == block[:, None]
    mixed = [attention(q[0], k[0], v[0], earlier | same)]
    for s in range(1, streams.shape[0]):
        # A noised block: the clean blocks before it, and itself.
        mixed.append(attention(
            q[s], jnp.concatenate([k[0], k[s]], axis=1),
            jnp.concatenate([v[0], v[s]], axis=1),
            jnp.concatenate([earlier, same], axis=1)))
    h = streams + jnp.einsum("sblhd,hde->sble", jnp.stack(mixed), small["wo"])
    m = rms_norm(h, small["mlp_norm"], eps)
    probs = jax.nn.softmax(m @ small["w_router"], axis=-1)
    weights, idx = lax.top_k(probs, model["num_experts_per_tok"])
    if model.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return h + experts(m, w, idx, weights), jnp.sort(idx, axis=-1)


def hidden_states(params, streams_of_tokens, model):
    """tokens [1 + S, B, L] (L a multiple of the block) -> the final
    norm's output [1 + S, B, L, E] and the routing [n, 1 + S, B, L, k]."""
    assert streams_of_tokens.shape[-1] % model["block_length"] == 0
    x = params["embed"]["tokens"].astype(F32)[streams_of_tokens]
    x, routing = lax.scan(lambda x, w: layer(x, w, model), x,
                          params["layers"])
    return rms_norm(x, params["final_norm"], model["rms_norm_eps"]), routing


def head(params, x):
    """x [.., E] -> logits [.., V], the vocabulary a slice at a time."""
    w = params["lm_head"]
    vocab = w.shape[1]
    if vocab % HEAD_SLICES:
        return x @ w.astype(F32)
    slices = w.reshape(w.shape[0], HEAD_SLICES, vocab // HEAD_SLICES)
    out = lax.map(lambda part: x @ part.astype(F32),
                  jnp.moveaxis(slices, 1, 0))           # [slices, .., V/s]
    return jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], vocab)


def block_forward(params, tokens, model, with_routing: bool = False):
    """tokens [B, L] -> logits [B, L, V] under the mask by blocks: what
    a pass computes for a block in flight that closes ``tokens`` (with
    the mask's id at its unfixed positions), all earlier blocks clean."""
    with jax.default_matmul_precision("highest"):
        x, routing = hidden_states(params, tokens[None], model)
        logits = head(params, x[0])
    return (logits, routing[:, 0]) if with_routing else logits


def fix_count(block_length: int, steps: int, done: int) -> int:
    """Masked positions the pass after ``done`` passes of a block fixes."""
    steps = min(max(int(steps), 1), block_length)
    return block_length // steps + (done < block_length % steps)


def generate(params, prompt, max_new_tokens: int, model,
             remasking: str = "sequential", denoising_steps=None,
             confidence_threshold: float = 0.9, record=None):
    """The loop of the module's docstring, greedy, for one prompt (a
    list of ids): the ``max_new_tokens`` generated ids. ``record``, a
    dict, receives position -> the float32 logits that decided it."""
    size, mask = model["block_length"], model["mask_token_id"]
    steps = denoising_steps or model["denoising_steps"]
    assert remasking in REMASKING, remasking
    total = math.ceil((len(prompt) + max_new_tokens) / size) * size
    logits_of = jax.jit(functools.partial(block_forward, model=model))
    known = list(prompt)
    while len(known) < len(prompt) + max_new_tokens:
        start = len(known) // size * size
        block = known[start:] + [None] * (start + size - len(known))
        done = 0
        while None in block:
            row = known[:start] + [mask if t is None else t for t in block]
            row += [0] * (total - len(row))  # later blocks: seen by none
            logits = np.asarray(logits_of(params, jnp.asarray([row])))[
                0, start:start + size].copy()
            logits[:, mask] = -np.inf
            x0 = logits.argmax(-1)
            shifted = logits - logits.max(-1, keepdims=True)
            c = 1.0 / np.exp(shifted).sum(-1)  # the argmax's probability
            masked = [i for i in range(size) if block[i] is None]
            n = fix_count(size, steps, done)
            by_confidence = sorted(masked, key=lambda i: (-c[i], i))
            if remasking == "sequential":
                chosen = masked[:n]
            elif remasking == "low_confidence_static":
                chosen = by_confidence[:n]
            else:
                over = [i for i in masked if c[i] > confidence_threshold]
                chosen = over if len(over) >= n else by_confidence[:n]
            for i in chosen:
                block[i] = int(x0[i])
                if record is not None:
                    record[start + i] = logits[i]
            done += 1
        known = known[:start] + block
    return known[len(prompt):len(prompt) + max_new_tokens]


def forward(params, tokens, model, with_routing: bool = False):
    """tokens [B, L] (a row's prompt and served tokens, then padding)
    -> [B, L, V] float32 in which entry ``p - 1`` holds the logits that
    decided position ``p`` (the module's docstring: every block
    replayed under the ``sequential`` rule and ``model``'s
    ``denoising_steps``; the mask's id at the row's least). With
    ``with_routing`` also the experts [n, B, L, k] (sorted) that the
    deciding pass chose at ``p``, at entry ``p - 1`` as well."""
    size, mask = model["block_length"], model["mask_token_id"]
    steps = min(max(int(model["denoising_steps"]), 1), size)
    rows, length = tokens.shape
    pad = -length % size
    tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
    offset = jnp.arange(length + pad) % size
    # Pass t sees the offsets its earlier passes fixed; it decides the
    # next fix_count of them.
    fixed_before = np.cumsum([0] + [fix_count(size, steps, t)
                                    for t in range(steps)])
    noised = [jnp.where(offset < int(fixed_before[t]), tokens, mask)
              for t in range(steps)]
    decided_by = jnp.searchsorted(jnp.asarray(fixed_before[1:]), offset,
                                  side="right")                 # [L]
    with jax.default_matmul_precision("highest"):
        x, routing = hidden_states(params, jnp.stack([tokens, *noised]),
                                   model)
        # Of each position, the stream of the pass that decided it.
        deciding = jnp.take_along_axis(
            x[1:], decided_by[None, None, :, None], axis=0)[0]  # [B, L, E]
        logits = head(params, deciding)
    logits = logits.at[..., mask].set(jnp.min(logits, axis=-1))
    logits = jnp.pad(logits[:, 1:length], ((0, 0), (0, 1), (0, 0)))
    if not with_routing:
        return logits
    chosen = jnp.take_along_axis(
        routing[:, 1:], decided_by[None, None, None, :, None], axis=1)[:, 0]
    return logits, jnp.pad(chosen[:, :, 1:length],
                           ((0, 0), (0, 0), (0, 1), (0, 0)))
