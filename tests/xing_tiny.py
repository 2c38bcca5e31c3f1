"""What ``test_xing.py`` (the programs driven by hand) and
``test_latent_engine.py`` (the same programs through ``LLMEngine``) share:
the tiny float32 Xing configuration, its numbers under the reference's
keys, and the plain float32 reference's logits
(``benchmark/reference/xing_decoder.py``). Two files so that ``--dist
loadfile`` can give the engines a worker of their own."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import xing_decoder as reference  # noqa: E402
from ray_tpu.models import xing  # noqa: E402

BLOCK, CHUNK, ROWS, TABLE = 4, 8, 4, 16      # a table of 64 positions


def tiny(**changes) -> xing.XingConfig:
    return xing.XingConfig.tiny(**{"dtype": jnp.float32, **changes})


def numbers(cfg: xing.XingConfig) -> dict:
    """What the reference is given: the configuration file's numbers
    under their Hugging Face keys."""
    out = {
        "hc_mult": cfg.hc_mult, "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
        "hc_eps": cfg.hc_eps, "mhc_h_res_clamp_min": cfg.hc_clamp_min,
        "mhc_h_res_clamp_max": cfg.hc_clamp_max,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling_factor}
    out.update({f"rope_scaling_{k}": v for k, v in cfg.yarn.items()})
    return out


_REFERENCE = {}


def reference_logits(cfg, params, context):
    """The reference's full forward; the context padded to the table's
    64 positions (causal: what follows a position changes nothing at
    it), so that it compiles once a configuration."""
    if cfg not in _REFERENCE:
        _REFERENCE[cfg] = jax.jit(lambda p, t: reference.forward(
            p, t, numbers(cfg)))
    padded = np.zeros((1, TABLE * BLOCK), np.int32)
    padded[0, :len(context)] = context
    return np.asarray(_REFERENCE[cfg](params, jnp.asarray(padded)))[
        0, :len(context)]


def contexts_of(lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n) for n in lengths]
