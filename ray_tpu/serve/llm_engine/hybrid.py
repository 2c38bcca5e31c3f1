"""The paged steps of a hybrid model (``models/phi4flash.py``): layers
of five kinds over THREE caches, one dict donated through every step.

- ``k``, ``v`` ``[1, num_blocks, bs, kv d]``: the ONE full-attention
  pool, paged by the same block tables and allocator as a dense model's
  (``kv_cache.PagedKVCache``). A position's 20 key heads of 64 lie side
  by side in one minor dimension of 1280 (heads of 64 would waste half
  of the chip's lanes). The full-attention layer writes it; it and
  every cross-attention layer read the same gathered view ``[B, S, kv
  d]``, gathered once a step and closed over by the scan of the
  cross-decoder. Layers that own no keys have no pool.
- ``win_k``, ``win_v`` ``[window layers, rows, ring, kv d]``: a ring of
  blocks a row for the window layers. Position ``p`` of the request in
  row slot ``r`` lies at ``[layer, r, p % ring]``; ``ring`` is the
  window, one prefill chunk and one block, rounded up to whole blocks
  (``ring_positions``: 656 at 512 + 128 + 16), whatever the context: a
  chunk's first token still reads the window - 1 positions before it
  after the chunk's last is written. Which position a ring entry holds
  follows from the newest position written, so nothing is ever zeroed:
  an entry the request has not written yet holds a position below 0 and
  is masked. The ring is read where it lies (a slice by layer, no
  gather).
- ``ssm`` ``[state layers, rows, Di, N]`` float32 and ``conv`` ``[state
  layers, rows, d_conv - 1, Di]``: the recurrent state, one slot a row.
  A chunk at position 0 starts from zeros IN THE PROGRAM, so a slot's
  last tenant and a preempted request's stale state can never show; a
  chunk's padding (positions at or past ``n_valid``) and an inactive
  decode row (position 0) advance nothing.

A request's row slot (``EngineRequest.slot``) is its row of the decode
step and its index into the ring and the state, so the decode step
reads and writes both where they lie. The layer pattern compiles as two
scans over periods with the two middle layers between them. The two
programs are ``model.py``'s, over ``forward`` here.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ray_tpu.models import phi4flash as phi
from ray_tpu.serve.llm_engine.model import Family, row_beside_zeros

F32 = jnp.float32


def ring_positions(config, block_size: int, chunk_len: int) -> int:
    """Positions a row's window ring holds: the window, a chunk and a
    block, in whole blocks."""
    need = config.sliding_window + chunk_len + block_size
    return -(-need // block_size) * block_size


def init_cache(config, num_blocks: int, block_size: int, rows: int,
               chunk_len: int) -> dict:
    width = config.num_kv_heads * config.head_dim
    ring = ring_positions(config, block_size, chunk_len)
    pool = (1, num_blocks, block_size, width)
    window = (config.window_layers, rows, ring, width)
    return {
        "k": jnp.zeros(pool, config.dtype),
        "v": jnp.zeros(pool, config.dtype),
        "win_k": jnp.zeros(window, config.dtype),
        "win_v": jnp.zeros(window, config.dtype),
        "ssm": jnp.zeros((config.ssm_layers, rows, config.d_inner,
                          config.d_state), config.state_dtype),
        "conv": jnp.zeros((config.ssm_layers, rows, config.d_conv - 1,
                           config.d_inner), config.dtype),
    }


def _ring_view(newest, ring: int):
    """The position each ring entry holds once ``newest`` [...] is the
    newest position written: ``[..., ring]``, below 0 where the request
    has not written the entry yet."""
    entries = jnp.arange(ring)
    return newest[..., None] - jnp.mod(newest[..., None] - entries, ring)


def _window_mask(held, positions, config):
    """held [B, ring], positions [B, T] -> [B, T, ring]: causal, inside
    the window, written by this request."""
    held, at = held[:, None, :], positions[:, :, None]
    return (held >= 0) & (held <= at) \
        & (at - held < config.sliding_window + config.window_shift)


class _Steps:
    """What differs between the two programs: where a row's state and
    ring lie and which form of the state-space mixer runs. ``x`` is
    ``[B, T, E]`` in both ([rows, 1] or [1, chunk])."""

    def __init__(self, config, block_size, cache, positions, tables,
                 valid):
        self.config, self.block_size = config, block_size
        self.positions, self.tables, self.valid = positions, tables, valid
        ring = cache["win_k"].shape[2]
        newest = jnp.max(jnp.where(valid, positions, -1), axis=1)   # [B]
        self.window_mask = _window_mask(_ring_view(newest, ring),
                                        positions, config)
        # An entry past the ring is dropped by the scatter.
        self.ring_at = jnp.where(valid, positions % ring, ring)
        S = tables.shape[1] * block_size
        self.full_mask = jnp.arange(S)[None, None, :] \
            <= positions[:, :, None]

    def window(self, w, layer, h, win_k, win_v, li):
        config = self.config
        q, k, v = phi.qkv_projection(w, h, config)
        at = self.ring_index(li)
        win_k = win_k.at[at].set(k.astype(win_k.dtype), mode="drop")
        win_v = win_v.at[at].set(v.astype(win_v.dtype), mode="drop")
        keys, values = self.ring_of(win_k, li), self.ring_of(win_v, li)
        out = phi.differential_attention(w, layer, q, keys, values,
                                         self.window_mask, config)
        return out, win_k, win_v

    def full(self, w, layer, h, pool_k, pool_v):
        """The layer whose keys and values are the cache: written by
        block table, then gathered ONCE for it and every cross layer."""
        config, bs = self.config, self.block_size
        q, k, v = phi.qkv_projection(w, h, config)
        blocks = jnp.take_along_axis(self.tables, self.positions // bs,
                                     axis=1)
        blocks = jnp.where(self.valid, blocks, 0)       # scratch block
        offsets = jnp.where(self.valid, self.positions % bs, 0)
        pool_k = pool_k.at[0, blocks, offsets].set(k.astype(pool_k.dtype))
        pool_v = pool_v.at[0, blocks, offsets].set(v.astype(pool_v.dtype))
        B, S = self.tables.shape[0], self.full_mask.shape[-1]
        keys = pool_k[0, self.tables].reshape(B, S, -1)
        values = pool_v[0, self.tables].reshape(B, S, -1)
        out = phi.differential_attention(w, layer, q, keys, values,
                                         self.full_mask, config)
        return out, pool_k, pool_v, keys, values

    def cross(self, w, layer, h, keys, values):
        return phi.differential_attention(
            w, layer, phi.q_projection(w, h, self.config), keys, values,
            self.full_mask, self.config)


class _DecodeSteps(_Steps):
    """Row ``i`` of the step is row slot ``i`` of the ring and the
    state."""

    def ring_index(self, li):
        return li, jnp.arange(self.positions.shape[0])[:, None], self.ring_at

    @staticmethod
    def ring_of(ring, li):
        return ring[li]

    def ssm(self, w, h, ssm, conv, li):
        out, memory, s, c = phi.ssm_step(w, h[:, 0], ssm[li], conv[li],
                                         self.valid[:, 0], self.config)
        return out[:, None], memory[:, None], ssm.at[li].set(s), \
            conv.at[li].set(c)


class _ChunkSteps(_Steps):
    """One request's chunk, in row slot ``slot``; a chunk at position 0
    starts from a zero state."""

    def __init__(self, *args, slot, n_valid):
        super().__init__(*args)
        self.slot, self.n_valid = slot, n_valid
        self.fresh = self.positions[0, 0] == 0

    def ring_index(self, li):
        return li, self.slot, self.ring_at

    def ring_of(self, ring, li):
        return ring[li, self.slot][None]

    def ssm(self, w, h, ssm, conv, li):
        s = jnp.where(self.fresh, 0, ssm[li, self.slot])
        c = jnp.where(self.fresh, 0, conv[li, self.slot])
        out, memory, s, c = phi.ssm_chunk(w, h[0], s, c, self.n_valid,
                                          self.config)
        return out[None], memory[None], ssm.at[li, self.slot].set(s), \
            conv.at[li, self.slot].set(c)


def forward(params: dict, cache: dict, tokens, positions, tables, config,
            block_size: int, *, slot=None, n_valid=None, logits_at=None):
    """tokens and positions [B, T], tables [B, M] -> (logits [B, T, V]
    float32, or [B, V] of position ``logits_at`` alone; the updated
    cache; None twice: no experts). Without ``n_valid`` it is a decode
    step: ``T == 1``, row ``i`` is row slot ``i``, a row at position 0
    is inactive. With it, one request's chunk in row slot ``slot``, its
    first ``n_valid`` positions real."""
    if n_valid is None:
        steps = _DecodeSteps(config, block_size, cache, positions, tables,
                             positions > 0)
    else:
        valid = jnp.arange(tokens.shape[1])[None, :] < n_valid
        steps = _ChunkSteps(config, block_size, cache, positions, tables,
                            valid, slot=slot, n_valid=n_valid)
    eps, half = config.layer_norm_eps, config.num_layers // 2
    # The residual stream is float32 (phi.residual_mlp says why).
    x = params["embed"]["tokens"][tokens].astype(F32)

    def norm(x, block):
        return phi.layer_norm(x, block["ln1"], eps, config.dtype)

    def front(carry, period):
        x, ssm, conv, win_k, win_v = carry
        w, li = period
        first, second = w["block_a"], w["block_b"]
        out, _, ssm, conv = steps.ssm(w["ssm"], norm(x, first), ssm, conv, li)
        x = phi.residual_mlp(x + out, first, config)
        out, win_k, win_v = steps.window(w["attn"], 2 * li + 1,
                                         norm(x, second), win_k, win_v, li)
        x = phi.residual_mlp(x + out, second, config)
        return (x, ssm, conv, win_k, win_v), None

    (x, ssm, conv, win_k, win_v), _ = lax.scan(
        front, (x, cache["ssm"], cache["conv"], cache["win_k"],
                cache["win_v"]),
        (params["front"], jnp.arange(config.front_periods)))

    w = params["mid_ssm"]
    out, memory, ssm, conv = steps.ssm(
        w["ssm"], norm(x, w["block"]), ssm, conv, config.ssm_layers - 1)
    x = phi.residual_mlp(x + out, w["block"], config)
    w = params["mid_attn"]
    out, pool_k, pool_v, keys, values = steps.full(
        w["attn"], half + 1, norm(x, w["block"]), cache["k"], cache["v"])
    x = phi.residual_mlp(x + out, w["block"], config)

    def back(x, period):
        w, pi = period
        first, second = w["block_a"], w["block_b"]
        x = phi.residual_mlp(
            x + phi.gmu(w["gmu"], norm(x, first), memory, config),
            first, config)
        out = steps.cross(w["cross"], half + 3 + 2 * pi, norm(x, second),
                          keys, values)
        return phi.residual_mlp(x + out, second, config), None

    x, _ = lax.scan(back, x, (params["back"],
                              jnp.arange(config.back_periods)))
    if logits_at is not None:
        x = row_beside_zeros(x, logits_at)
    x = phi.layer_norm(x, params["final_norm"], eps, config.dtype)
    # The tied head, with the table as the product's LEFT operand (its
    # rows contracted as they lie): as the right one the chip's compiler
    # first writes a transposed copy of the whole table, every step.
    table = params["embed"]["tokens"].astype(config.dtype)
    logits = lax.dot_general(table, x, (((1,), (2,)), ((), ())),
                             preferred_element_type=F32)        # [V, B, T]
    logits = jnp.moveaxis(logits, 0, -1)
    if logits_at is not None:
        logits = logits[:, 0]
    return logits, {"k": pool_k, "v": pool_v, "win_k": win_k,
                    "win_v": win_v, "ssm": ssm, "conv": conv}, None, None


FAMILY = Family(
    # Held in the dtype it is served in from the draw on: what
    # ``serving_params`` then casts is already cast.
    init_params=lambda config, key: phi.init_params(config, key,
                                                    config.dtype),
    init_cache=init_cache,
    forward=forward,
    ring_positions=ring_positions,
    recurrent=True,
)
