"""Phi-4-mini-flash-reasoning's decoder-hybrid-decoder (``phi4flash``,
arXiv:2507.06607 "SambaY"): a self-decoder of state-space and
window-attention layers, one full-attention layer whose keys and values
are THE cache, and a cross-decoder of gated memory units and
cross-attention layers that read that one cache. No positional encoding.

Block, every layer: ``x = x + Mixer(LN(x)); x = x + MLP(LN(x))``,
LayerNorm with scale and bias, ``MLP(u) = W2 (silu(g) * y)``, ``[g, y] =
W1 u``. With ``n`` layers and ``half = n // 2`` the mixer of layer ``l``:

- ``l < half``: even ``l`` state-space (Mamba-1), odd ``l`` differential
  attention over the last ``sliding_window`` positions;
- ``l == half``: state-space, which also gives its memory ``M`` (its
  output BEFORE the gate) to the gated memory units;
- ``l == half + 1``: full differential attention; its keys and values
  are read again by every cross-attention layer;
- ``l >= half + 2``: even ``l`` a gated memory unit ``W2 (silu(W1 h) *
  M)``, odd ``l`` cross-attention (a query projection only).

The parameter tree stacks what repeats so that the stack compiles as
two scans: ``front`` holds the ``half // 2`` periods [state-space,
window], ``mid_ssm`` and ``mid_attn`` layers ``half`` and ``half + 1``,
``back`` the periods [memory unit, cross]. A period's two blocks (norms
and MLP) are ``block_a`` and ``block_b``, each stacked over the periods
alone: a scan then takes a layer's matrices by their leading axis, which
costs no copy.

Each mixer comes in the two forms a serving engine needs: a chunk of
one row that starts from a carried state (``*_chunk``), and one token
for every row (``*_step``); attention and the memory unit are one
function for both (a chunk is ``[1, C]``, a step ``[rows, 1]``). The
recurrence and its state are float32; matrix products run on operands
in ``config.dtype``. ``serve/llm_engine/hybrid.py`` puts them over the
three caches.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import rms_norm

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    # Not keys of the published config.json (modeling_phi4flash.py).
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dtype: Any = jnp.bfloat16
    # Controls that a test must see FAIL (never set by a configuration):
    # the recurrent state kept in another dtype, the window's mask moved
    # by this many positions, differential attention's lambda dropped.
    state_dtype: Any = jnp.float32
    window_shift: int = 0
    drop_lambda: bool = False

    #: Which forward, cache and weights ``serve/llm_engine`` gives it.
    family = "hybrid"
    num_experts = 0

    def __post_init__(self):
        if self.num_layers % 4 or self.num_layers < 8:
            raise ValueError("the layer pattern needs a multiple of 4 "
                             f"layers, at least 8; got {self.num_layers}")
        if self.mb_per_layer != 2:
            raise ValueError("only mb_per_layer 2 (a state-space layer "
                             "every second layer) is written down")
        if self.num_heads % 4 or self.num_kv_heads * 2 != self.num_heads:
            raise ValueError("differential attention pairs the heads: "
                             "num_heads = 2 x num_kv_heads, a multiple of 4")

    @staticmethod
    def tiny(vocab_size: int = 256, **changes) -> "Phi4FlashConfig":
        """Test size: 8 layers (2 periods, the two middle layers, 1
        period), a window of 8 that short contexts cross."""
        base = dict(vocab_size=vocab_size, hidden_size=64,
                    intermediate_size=128, num_layers=8, num_heads=4,
                    num_kv_heads=2, sliding_window=8, max_seq_len=128)
        return Phi4FlashConfig(**{**base, **changes})

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return math.ceil(self.hidden_size / 16)

    @property
    def front_periods(self) -> int:
        return self.num_layers // 4

    @property
    def back_periods(self) -> int:
        return self.num_layers // 4 - 1

    @property
    def ssm_layers(self) -> int:
        return self.front_periods + 1

    @property
    def window_layers(self) -> int:
        return self.front_periods

    @property
    def num_params(self) -> int:
        e, m, di = self.hidden_size, self.intermediate_size, self.d_inner
        n, r = self.d_state, self.dt_rank
        kvd = self.num_kv_heads * self.head_dim
        block = 4 * e + 3 * e * m                    # two LayerNorms, MLP
        ssm = (e * 2 * di + self.d_conv * di + di + di * (r + 2 * n)
               + r * di + di + di * n + di + di * e)
        lambdas = 4 * self.head_dim + 2 * self.head_dim  # and the sub-norm
        attn = e * (e + 2 * kvd) + e + 2 * kvd + lambdas + e * e + e
        cross = e * e + e + lambdas + e * e + e
        memory_unit = 2 * e * di
        return (self.vocab_size * e + 2 * e + self.num_layers * block
                + self.ssm_layers * ssm + (self.window_layers + 1) * attn
                + self.back_periods * (memory_unit + cross))


# ------------------------------------------------------------------ weights


# Most elements of one draw of random numbers (``scaled_normal``).
DRAW = 1 << 20


def scaled_normal(key: jax.Array, shape: tuple, scale: float,
                  dtype=F32) -> jax.Array:
    """``normal(key, shape) * scale`` drawn in float32, held in
    ``dtype``. A tensor of more than ``DRAW`` elements is drawn a block
    of whole rows at a time inside one loop, each block from the key
    folded with its index and cast before the next is drawn: the
    chip's compiler takes as long over a random tensor as the tensor is
    large, and the float32 draw of the whole never exists. (The
    initialisation of the published widths compiled for the v5e in
    24.6 s in one draw a tensor and in 9.2 s so, by the deviceless
    compile of PR 33; on the chip's own host, from an empty cache, the
    first took long enough to fail a replica's health probe.)"""
    cols = shape[-1]
    rows = math.prod(shape) // cols
    if rows * cols <= DRAW or len(shape) < 2:
        return (jax.random.normal(key, shape, F32) * scale).astype(dtype)
    block = next(b for b in range(min(rows, max(1, DRAW // cols)), 0, -1)
                 if rows % b == 0)

    def draw(i):
        return (jax.random.normal(jax.random.fold_in(key, i),
                                  (block, cols), F32) * scale).astype(dtype)

    return lax.map(draw, jnp.arange(rows // block)).reshape(shape)


def init_params(config: Phi4FlashConfig, key: jax.Array,
                dtype=F32) -> dict:
    """Seeded weights, held in ``dtype``, in the tree the module's
    docstring describes. Matrices are normal with variance 1 / fan-in,
    the embedding too (it is also the output head: logits of unit
    variance); ``A_log`` and ``dt_bias`` as Mamba initialises them
    (``A = -(1..N)``, a step between 1e-3 and 1e-1)."""
    e, m, di = config.hidden_size, config.intermediate_size, config.d_inner
    n, r, d = config.d_state, config.dt_rank, config.head_dim
    qkv = e + 2 * config.num_kv_heads * d
    keys = iter(jax.random.split(key, 128))

    def dense(fan_in, *shape):
        return scaled_normal(next(keys), shape, fan_in ** -0.5, dtype)

    def small(*shape):  # vectors: float32 until the tree is cast
        return scaled_normal(next(keys), shape, 0.1)

    def norm(*lead):
        return {"scale": 1.0 + small(*lead, e), "bias": small(*lead, e)}

    def block(*lead):
        return {"ln1": norm(*lead), "ln2": norm(*lead),
                "w1": dense(e, *lead, e, 2 * m), "w2": dense(m, *lead, m, e)}

    def ssm(*lead):
        step = jnp.exp(jax.random.uniform(next(keys), (*lead, di), F32)
                       * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {
            "in_proj": dense(e, *lead, e, 2 * di),
            "conv_w": dense(config.d_conv, *lead, config.d_conv, di),
            "conv_b": small(*lead, di),
            "x_proj": dense(di, *lead, di, r + 2 * n),
            "dt_proj": dense(r, *lead, r, di),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=F32)), (*lead, di, n)),
            "D": jnp.ones((*lead, di), F32),
            "out_proj": dense(di, *lead, di, e)}

    def differential(*lead):
        return {**{name: small(*lead, d) for name in
                   ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")},
                "subln": 1.0 + small(*lead, 2 * d),
                "wo": dense(e, *lead, e, e), "bo": small(*lead, e)}

    def attn(*lead):
        return {"wqkv": dense(e, *lead, e, qkv), "bqkv": small(*lead, qkv),
                **differential(*lead)}

    def cross(*lead):
        return {"wq": dense(e, *lead, e, e), "bq": small(*lead, e),
                **differential(*lead)}

    def memory_unit(*lead):
        return {"w1": dense(e, *lead, e, di), "w2": dense(di, *lead, di, e)}

    p1, p2 = config.front_periods, config.back_periods
    return jax.tree.map(lambda x: x.astype(dtype), {
        "embed": {"tokens": dense(e, config.vocab_size, e)},
        "front": {"block_a": block(p1), "block_b": block(p1),
                  "ssm": ssm(p1), "attn": attn(p1)},
        "mid_ssm": {"block": block(), "ssm": ssm()},
        "mid_attn": {"block": block(), "attn": attn()},
        "back": {"block_a": block(p2), "block_b": block(p2),
                 "gmu": memory_unit(p2), "cross": cross(p2)},
        "final_norm": norm(),
    })


# ------------------------------------------------------------- shared parts


def layer_norm(x, w: dict, eps: float, dtype):
    """LayerNorm in float32; the result, a product's operand, in
    ``dtype``."""
    x32 = x.astype(F32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    out = (x32 - mean) * lax.rsqrt(var + eps)
    return (out * w["scale"].astype(F32) + w["bias"].astype(F32)) \
        .astype(dtype)


def matmul(x, w, dtype):
    """``x @ w`` on operands and with a result in ``dtype`` (the chip
    accumulates such a product in float32)."""
    return jnp.einsum("...e,ef->...f", x.astype(dtype), w.astype(dtype))


def matmul_f32(x, w):
    """A small product whose result steers the recurrence: float32
    operands at the highest precision (on the chip a float32 product
    otherwise rounds its operands to bfloat16)."""
    return jnp.einsum("...e,ef->...f", x.astype(F32), w.astype(F32),
                      precision=lax.Precision.HIGHEST)


def mlp(h, w: dict, config: Phi4FlashConfig):
    gate, up = jnp.split(matmul(h, w["w1"], config.dtype), 2, axis=-1)
    return matmul(jax.nn.silu(gate) * up, w["w2"], config.dtype)


def residual_mlp(x, w: dict, config: Phi4FlashConfig):
    """``x + MLP(LN(x))``. The residual stream ``x`` is float32: 64
    additions in bfloat16 would each round the whole stream, which was
    most of the distance to the float32 reference (0.25 of a logit's
    standard deviation on the chip, PR 33); it is 10 KiB a row."""
    return x + mlp(layer_norm(x, w["ln2"], config.layer_norm_eps,
                              config.dtype), w, config)


# -------------------------------------------------------------- state space


def _ssm_inputs(w: dict, u, config):
    """From the convolved, activated ``u`` [..., Di]: the step ``dt``
    [..., Di], ``B`` and ``C`` [..., N] (float32) and ``A`` [Di, N]. A
    mixer with ``dt_norm``, ``b_norm`` and ``c_norm`` (``models/jamba.py``)
    passes the three outputs of ``x_proj`` through an RMSNorm each
    (``config.rms_norm_eps``) first; Phi's has none."""
    r, n = config.dt_rank, config.d_state
    proj = matmul_f32(u, w["x_proj"])
    dt_r, b, c = proj[..., :r], proj[..., r:r + n], proj[..., r + n:]
    if "dt_norm" in w:
        eps = config.rms_norm_eps
        dt_r, b, c = (rms_norm(x, w[name], eps) for x, name in (
            (dt_r, "dt_norm"), (b, "b_norm"), (c, "c_norm")))
    dt = jax.nn.softplus(matmul_f32(dt_r, w["dt_proj"])
                         + w["dt_bias"].astype(F32))
    return dt, b, c, -jnp.exp(w["A_log"].astype(F32))


def _ssm_update(s, dt, u, b, c, a, state_dtype):
    """One position of the recurrence for states ``s`` [..., Di, N]:
    ``s = exp(dt A) s + (dt u) (x) B``, ``y = s . C`` (without ``D u``).
    A step of ``dt == 0`` leaves the state as it was."""
    s = jnp.exp(dt[..., None] * a) * s.astype(F32) \
        + (dt * u.astype(F32))[..., None] * b[..., None, :]
    s = s.astype(state_dtype)
    return s, jnp.einsum("...dn,...n->...d", s.astype(F32), c)


def _ssm_out(w: dict, y, u, z, config):
    """(the layer's output, its memory ``y`` before the gate)."""
    y = (y + w["D"].astype(F32) * u.astype(F32)).astype(config.dtype)
    return matmul(y * jax.nn.silu(z), w["out_proj"], config.dtype), y


def ssm_step(w: dict, h, s, conv, active, config, taps_first=False):
    """One token for every row. h [B, E]; s [B, Di, N]; conv [B,
    d_conv - 1, Di] (the convolution's last inputs), or with
    ``taps_first`` [d_conv - 1, B, Di] (``llm_engine/mamba.py``: the
    rows before the minor dimension, where the chip tiles them and not
    the three taps); active [B] bool: an inactive row's state is not
    advanced. Returns (out [B, E], memory [B, Di] (the output before
    the gate, which only Phi's gated memory units read), s, conv)."""
    u, z = jnp.split(matmul(h, w["in_proj"], config.dtype), 2, axis=-1)
    if taps_first:
        window = jnp.concatenate([conv, u[None].astype(conv.dtype)], axis=0)
        conv = jnp.where(active[None, :, None], window[1:], conv)
        taps = "kbd,kd->bd"
    else:
        window = jnp.concatenate([conv, u[:, None].astype(conv.dtype)],
                                 axis=1)
        conv = jnp.where(active[:, None, None], window[:, 1:], conv)
        taps = "bkd,kd->bd"
    u = jax.nn.silu(
        (jnp.einsum(taps, window.astype(F32), w["conv_w"].astype(F32))
         + w["conv_b"].astype(F32)).astype(config.dtype))
    dt, b, c, a = _ssm_inputs(w, u, config)
    dt = jnp.where(active[:, None], dt, 0.0)
    s, y = _ssm_update(s, dt, u, b, c, a, config.state_dtype)
    return (*_ssm_out(w, y, u, z, config), s, conv)


def ssm_chunk(w: dict, h, s, conv, n_valid, config):
    """A chunk of one row from a carried state. h [C, E]; s [Di, N];
    conv [d_conv - 1, Di]; positions at or past ``n_valid`` are padding
    and advance nothing. Returns (out [C, E], memory [C, Di], s,
    conv)."""
    length, k = h.shape[0], config.d_conv
    u, z = jnp.split(matmul(h, w["in_proj"], config.dtype), 2, axis=-1)
    window = jnp.concatenate([conv, u.astype(conv.dtype)], axis=0)
    conv = lax.dynamic_slice_in_dim(window, n_valid, k - 1, axis=0)
    taps = jnp.stack([window[i:i + length] for i in range(k)], axis=1)
    u = jax.nn.silu(
        (jnp.einsum("ckd,kd->cd", taps.astype(F32), w["conv_w"].astype(F32))
         + w["conv_b"].astype(F32)).astype(config.dtype))
    dt, b, c, a = _ssm_inputs(w, u, config)
    dt = jnp.where((jnp.arange(length) < n_valid)[:, None], dt, 0.0)

    def position(s, inputs):
        return _ssm_update(s, *inputs, a, config.state_dtype)

    s, y = lax.scan(position, s, (dt, u, b, c))
    return (*_ssm_out(w, y, u, z, config), s, conv)


# --------------------------------------------------- differential attention


def lambda_of(w: dict, layer, config: Phi4FlashConfig):
    """(lambda, lambda_init) of a layer; ``layer`` may be traced."""
    init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))
    if config.drop_lambda:
        return jnp.zeros((), F32), init
    lam = jnp.exp(jnp.sum(w["lambda_q1"].astype(F32)
                          * w["lambda_k1"].astype(F32))) \
        - jnp.exp(jnp.sum(w["lambda_q2"].astype(F32)
                          * w["lambda_k2"].astype(F32))) + init
    return lam, init


def qkv_projection(w: dict, h, config: Phi4FlashConfig):
    """h [..., E] -> q [..., H, D] and k, v [..., KV D]: a position's
    key heads side by side, as they lie in the caches (one minor
    dimension of 1280 fills the chip's lanes; heads of 64 waste half of
    them)."""
    e, kv = config.hidden_size, config.num_kv_heads * config.head_dim
    out = matmul(h, w["wqkv"], config.dtype) + w["bqkv"].astype(config.dtype)
    q = out[..., :e]
    return (q.reshape(*q.shape[:-1], config.num_heads, config.head_dim),
            out[..., e:e + kv], out[..., e + kv:])


def q_projection(w: dict, h, config: Phi4FlashConfig):
    out = matmul(h, w["wq"], config.dtype) + w["bq"].astype(config.dtype)
    return out.reshape(*out.shape[:-1], config.num_heads, config.head_dim)


def differential_attention(w: dict, layer, q, keys, values, mask,
                           config: Phi4FlashConfig):
    """q [B, T, H, D] over keys and values [B, S, KV D] as they lie in a
    cache (a position's heads side by side; never repeated per query
    head, never copied into another order); mask [B, T, S] says which
    key a query may read. Query heads are 20 pairs ``(q1, q2)``, key
    heads G = 10 pairs ``(k1, k2)``, value heads 10 pairs concatenated
    to ``vv`` of 2 D; query pair ``p`` reads key-value pair ``p // 2``,
    so key pair ``g`` serves the four query heads ``4 g + 2 r + c``
    (pair ``2 g + r``, half ``c``).

    Each query head is laid in its own 64 of a row as wide as a
    position's keys, beside zeros, and contracted with the position's
    whole row: its product with its own key head exactly, as ONE matrix
    product a row of the batch whose operand is the cache as it lies. Of
    the weighted sum over whole rows of values each pair keeps its own
    128 columns. That is G times the arithmetic the pairs need, and it
    is cheap where it is paid: reading the keys takes longer, and any
    other order of the keys costs a copy of the gathered pool a step
    (4 to 7 ms by the compiler's own estimate at 32 rows x 4096). Both
    softmaxes and their difference are float32; the difference is cast
    once and contracted with the values."""
    dtype, d = config.dtype, config.head_dim
    B, T = q.shape[:2]
    groups = config.num_kv_heads // 2
    lam, init = lambda_of(w, layer, config)
    own = jnp.eye(2 * groups, dtype=dtype)               # head h, key head k
    q = q.astype(dtype).reshape(B, T, groups, 2, 2, d)   # g, r, c
    # Query head (g, r, c) reads key head k = 2 g + c.
    q = jnp.einsum("btgrcd,gchk->btgrchkd", q,
                   own.reshape(groups, 2, groups, 2))
    q = q.reshape(B, T, 4 * groups, 2 * groups * d)
    scores = jnp.einsum("btjc,bsc->bjts", q.astype(F32), keys.astype(F32))
    scores = jnp.where(mask[:, None], scores * d ** -0.5, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = probs.reshape(B, groups, 2, 2, T, -1)        # g, r, c
    probs = (probs[:, :, :, 0] - lam * probs[:, :, :, 1]).astype(dtype)
    out = jnp.einsum("bgrts,bsc->btgrc", probs.astype(F32),
                     values.astype(F32))
    out = jnp.einsum("btgrhf,gh->btgrf",
                     out.reshape(B, T, groups, 2, groups, 2 * d),
                     jnp.eye(groups, dtype=F32))
    out = out * lax.rsqrt(jnp.mean(jnp.square(out), -1, keepdims=True)
                          + config.layer_norm_eps) \
        * w["subln"].astype(F32) * (1.0 - init)
    out = out.astype(dtype).reshape(B, T, config.hidden_size)
    return matmul(out, w["wo"], dtype) + w["bo"].astype(dtype)


def gmu(w: dict, h, memory, config: Phi4FlashConfig):
    """The gated memory unit: ``W2 (silu(W1 h) * M)``."""
    gate = jax.nn.silu(matmul(h, w["w1"], config.dtype))
    return matmul(gate * memory.astype(config.dtype), w["w2"], config.dtype)
