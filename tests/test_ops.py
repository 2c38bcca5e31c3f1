"""Pallas kernel tests (interpret mode on the CPU test platform).

Each op is checked against its plain-JAX reference for values AND
gradients — the pattern for every kernel added to ray_tpu.ops.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention, rms_norm
from ray_tpu.parallel.ring_attention import plain_attention


def _qkv(b=2, l=128, h=4, kvh=4, d=32, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (b, l, h, d), dtype=dtype)
    k = jax.random.normal(keys[1], (b, l, kvh, d), dtype=dtype)
    v = jax.random.normal(keys[2], (b, l, kvh, d), dtype=dtype)
    return q, k, v


def test_flash_attention_matches_plain_causal():
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_flash_attention_noncausal():
    q, k, v = _qkv(l=64)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    ref = plain_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_flash_attention_gqa():
    q, k, v = _qkv(h=8, kvh=2)
    reps = 4
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = plain_attention(q, jnp.repeat(k, reps, axis=2),
                          jnp.repeat(v, reps, axis=2), causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_flash_attention_uneven_blocks():
    # seq not a multiple of the requested block → block clamps.
    q, k, v = _qkv(l=96)
    out = flash_attention(q, k, v, causal=True, block_q=96, block_k=32)
    ref = plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_flash_attention_grads_match():
    q, k, v = _qkv(l=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(plain_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_attention_grads_match_noncausal():
    q, k, v = _qkv(l=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=False,
                                       block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(plain_attention(q, k, v, causal=False) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_attention_grads_uneven_blocks():
    # Gradient path with non-dividing requested blocks (clamped) and GQA.
    q, k, v = _qkv(l=96, h=8, kvh=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=96, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(plain_attention(
            q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2),
            causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_attention_jit_compatible():
    q, k, v = _qkv(l=64)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    out = f(q, k, v)
    np.testing.assert_allclose(
        out, plain_attention(q, k, v, causal=True), atol=1e-5, rtol=1e-5)


def test_llama_flash_attention_config():
    from ray_tpu.models import llama

    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), attention="flash", dtype=jnp.float32)
    cfg_plain = dataclasses.replace(cfg, attention="plain")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                              cfg.vocab_size)
    out_flash = llama.forward(params, toks, cfg)
    out_plain = llama.forward(params, toks, cfg_plain)
    np.testing.assert_allclose(out_flash, out_plain, atol=2e-3, rtol=1e-3)


def test_rms_norm_matches_reference():
    from ray_tpu.models.llama import rms_norm as rms_ref

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 128))
    s = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0
    np.testing.assert_allclose(
        rms_norm(x, s), rms_ref(x, s, 1e-5), atol=1e-6, rtol=1e-6)


def test_rms_norm_grads():
    from ray_tpu.models.llama import rms_norm as rms_ref

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    s = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0
    g1 = jax.grad(lambda x, s: jnp.sum(rms_norm(x, s) ** 3),
                  argnums=(0, 1))(x, s)
    g2 = jax.grad(lambda x, s: jnp.sum(rms_ref(x, s, 1e-5) ** 3),
                  argnums=(0, 1))(x, s)
    np.testing.assert_allclose(g1[0], g2[0], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(g1[1], g2[1], atol=1e-3, rtol=1e-4)


def test_flash_attention_non_divisible_seq():
    """Regression: seq lengths that don't divide the block must not drop
    tail rows/keys (blocks auto-shrink to a divisor)."""
    q, k, v = _qkv(l=200)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    g1 = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(plain_attention(q, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _pallas_calls(sub)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("l, block_q, block_k, h, kvh", [
    (256, 64, 128, 4, 4),    # four q tiles over two k tiles
    (256, 128, 64, 4, 4),    # and the other way round
    (256, 64, 128, 8, 2),    # 4 query heads a key-value head
    (200, 64, 128, 4, 2),    # _fit_block: 50 and 100
    (231, 64, 128, 2, 2),    # odd divisors: 33 and 77, three tiles by seven
    (256, 64, 64, 4, 2),     # equal tiles: the crossed block is straight-line
    (67, 64, 64, 2, 2),      # a prime length: tiles of ONE position
])
def test_flash_attention_over_several_blocks(l, block_q, block_k, h, kvh,
                                             causal):
    """Several blocks a row with block_q != block_k: the unmasked and
    the masked loops of all three kernels both run, and where the
    diagonal crosses a block is not where the indices are equal. The
    forward and the three gradients against the plain form; and lse and
    delta cross the kernels' boundaries as rows."""
    q, k, v = _qkv(l=l, h=h, kvh=kvh, seed=l + block_q)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k)

    def plain(q, k, v):
        k, v = (jnp.repeat(x, h // kvh, axis=2) for x in (k, v))
        return plain_attention(q, k, v, causal=causal)

    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), atol=1e-5,
                               rtol=1e-5)
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))
             for f in (flash, plain)]
    for got, want in zip(grads[0](q, k, v), grads[1](q, k, v)):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    calls = list(_pallas_calls(jax.make_jaxpr(grads[0])(q, k, v).jaxpr))
    assert len(calls) == 3, calls
    for eqn in calls:
        for var in (*eqn.invars, *eqn.outvars):
            assert not (var.aval.dtype == jnp.float32
                        and var.aval.shape[-1] == 1), (
                eqn.params["name"], var.aval)
    rows = [var.aval.shape for eqn in calls for var in eqn.outvars
            if var.aval.dtype == jnp.float32 and var.aval.shape[-2:] == (1, l)]
    assert len(rows) == 2, rows  # the forward's lse, dq's delta


# ------------------------------------------------------------ kernel names


def _flash_loss(q, k, v):
    return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32))


@pytest.mark.parametrize("kernel, traced", [
    ("flash_fwd", lambda: jax.make_jaxpr(_flash_loss)(*_qkv())),
    ("flash_bwd_dq", lambda: jax.make_jaxpr(
        jax.grad(_flash_loss, argnums=(0, 1, 2)))(*_qkv())),
    ("flash_bwd_dkv", lambda: jax.make_jaxpr(
        jax.grad(_flash_loss, argnums=(0, 1, 2)))(*_qkv())),
    ("rmsnorm_fwd", lambda: jax.make_jaxpr(rms_norm)(
        jnp.ones((4, 64)), jnp.ones((64,)))),
])
def test_pallas_calls_carry_their_kernel_name(kernel, traced):
    """A profiler trace tells kernels apart by the name their custom
    call carries; the per-kernel shares of the benchmark select by
    it."""
    import re

    names = set(re.findall(r"\bname=(\w+)", str(traced())))
    assert kernel in names, names


@pytest.mark.parametrize("saved, forwards", [(True, 1), (False, 2)])
@pytest.mark.parametrize("kvh", [4, 2], ids=["equal", "grouped"])
def test_checkpoint_policy_can_keep_the_kernels_residuals(kvh, saved,
                                                          forwards):
    """``SAVED_NAMES`` tag o and lse inside the custom_vjp's forward
    rule: a jax.checkpoint that saves them differentiates to ONE
    forward kernel, one that saves nothing runs it again, and the
    gradients are the same."""
    import re

    from ray_tpu.ops.flash_attention import SAVED_NAMES

    policy = jax.checkpoint_policies.save_only_these_names(
        *SAVED_NAMES) if saved else None
    q, k, v = _qkv(kvh=kvh)
    grad = jax.grad(jax.checkpoint(_flash_loss, policy=policy),
                    argnums=(0, 1, 2))
    kernels = re.findall(r"\bname=(flash_\w+)",
                         str(jax.make_jaxpr(grad)(q, k, v)))
    assert kernels.count("flash_fwd") == forwards, kernels
    for got, want in zip(grad(q, k, v),
                         jax.grad(_flash_loss, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
