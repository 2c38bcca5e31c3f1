"""What ``test_jamba.py`` (the programs driven by hand) and
``test_jamba_engine.py`` (the same programs through ``LLMEngine``)
share: the tiny float32 Jamba configuration, its numbers under the
reference's keys, and the plain float32 reference's logits
(``benchmark/reference/jamba_decoder.py``). Two files so that ``--dist
loadfile`` can give the engines a worker of their own."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import jamba_decoder as reference  # noqa: E402
from ray_tpu.models import jamba  # noqa: E402

BLOCK, CHUNK, ROWS, TABLE = 4, 8, 4, 16      # a table of 64 positions


def tiny(**changes) -> jamba.JambaConfig:
    return jamba.JambaConfig.tiny(**{"dtype": jnp.float32, **changes})


def numbers(cfg) -> dict:
    """What the reference is given: the configuration file's numbers
    under their Hugging Face keys."""
    return {"rms_norm_eps": cfg.rms_norm_eps,
            "attn_layer_period": cfg.attn_layer_period,
            "attn_layer_offset": cfg.attn_layer_offset}


_REFERENCE = {}


def reference_logits(cfg, params, context):
    """The reference's full forward, the context padded to the table's
    64 positions (causal: what follows a position changes nothing at
    it)."""
    key = (cfg.attn_layer_period, cfg.attn_layer_offset, cfg.rms_norm_eps)
    if key not in _REFERENCE:
        _REFERENCE[key] = jax.jit(lambda p, t: reference.forward(
            p, t, numbers(cfg)))
    padded = np.zeros((1, TABLE * BLOCK), np.int32)
    padded[0, :len(context)] = context
    return np.asarray(_REFERENCE[key](params, jnp.asarray(padded)))[
        0, :len(context)]


def contexts_of(lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n) for n in lengths]
