"""ray_tpu.ops — pallas TPU kernels for the hot ops.

The reference delegates all device kernels to torch/CUDA; here they are
first-class: blockwise flash attention (flash_attention.py), fused
elementwise kernels (fused.py), the decode step's absorbed latent
attention through the block tables (paged_latent_attention.py), its
grouped softmax attention over key and value pools through the same
tables (paged_kv_attention.py), one
position of the gated delta rule on the state where it lies
(kda_state_update.py) and the feed-forward of the experts a serving
pass touched, out of the layers' stacked weights
(grouped_expert_ffn.py); the serving engine's families import the last
four from their modules. The training ops are
differentiable (custom vjp); every op falls back to pallas interpret
mode off-TPU so the same code path runs in CPU tests.
"""

from ray_tpu.ops.flash_attention import flash_attention, flash_attention_gspmd
from ray_tpu.ops.fused import rms_norm

__all__ = ["flash_attention", "flash_attention_gspmd", "rms_norm"]
