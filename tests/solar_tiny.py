"""What ``test_solar_open2.py`` (the programs driven by hand) and
``test_solar_engine.py`` (the same programs through ``LLMEngine``)
share: the tiny float32 Solar-Open2 configuration, its numbers under the
reference's keys, and the plain float32 reference's logits
(``benchmark/reference/solar_open2_decoder.py``). Two files so that
``--dist loadfile`` can give the engines a worker of their own."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import solar_open2_decoder as reference  # noqa: E402
from ray_tpu.models import solar_open2 as solar  # noqa: E402

BLOCK, CHUNK, ROWS, TABLE = 4, 8, 4, 16      # a table of 64 positions


def tiny(**changes) -> solar.SolarOpen2Config:
    return solar.SolarOpen2Config.tiny(**{"dtype": jnp.float32, **changes})


def numbers(cfg) -> dict:
    """What the reference is given: the configuration file's numbers
    under their Hugging Face keys and the flat copies."""
    return {"rms_norm_eps": cfg.rms_norm_eps,
            "num_experts_per_tok": cfg.experts_per_token,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "linear_attn_num_heads": cfg.kda_heads,
            "linear_attn_head_dim": cfg.kda_head_dim,
            "linear_attn_short_conv_kernel_size": cfg.conv_kernel,
            "n_routed_experts_routed_over": cfg.num_experts,
            "first_expert_held": cfg.first_expert,
            "kda_beta_scale": cfg.kda_beta_scale}


_REFERENCE = {}


def reference_logits(cfg, params, context, with_states=False):
    """The reference's full forward, the context padded to the table's
    64 positions (causal: what follows a position changes nothing at
    it; the STATES are taken unpadded)."""
    key = (dataclasses.replace(cfg, dtype=jnp.float32,
                               state_dtype=jnp.float32), with_states)
    if key not in _REFERENCE:
        _REFERENCE[key] = jax.jit(lambda p, t: reference.forward(
            p, t, numbers(cfg), with_states=with_states))
    if with_states:
        logits, states = _REFERENCE[key](params, jnp.asarray(context)[None])
        return np.asarray(logits)[0], states
    padded = np.zeros((1, TABLE * BLOCK), np.int32)
    padded[0, :len(context)] = context
    return np.asarray(_REFERENCE[key](params, jnp.asarray(padded)))[
        0, :len(context)]


def contexts_of(lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n) for n in lengths]
