"""ray_tpu.serve — model serving on the actor runtime.

Reference: python/ray/serve — @serve.deployment (api.py:246), serve.run
(:439), controller/replica/router/pow-2 scheduling, @serve.batch
(batching.py:436), long-poll config fan-out (long_poll.py), autoscaling.

TPU-native specifics live in ray_tpu.serve.llm_engine: a paged
KV-cache continuous-batching inference engine (prefill/decode
scheduling, gather-by-block-table attention, latency-driven replica
autoscaling) so many HTTP requests share one MXU-friendly decode
batch.
"""

from ray_tpu.serve.api import (
    delete,
    get_app_handle,
    get_deployment_handle,
    run,
    shutdown,
    start,
    status,
)
from ray_tpu.serve.batching import batch
from ray_tpu.serve.config import AutoscalingConfig, DeploymentConfig, HTTPOptions
from ray_tpu.serve.deployment import Application, Deployment, deployment
from ray_tpu.serve.multiplex import get_multiplexed_model_id, multiplexed
from ray_tpu.serve.router import (
    DeploymentHandle,
    DeploymentResponse,
    DeploymentStreamingResponse,
)

__all__ = [
    "Application", "AutoscalingConfig", "Deployment", "DeploymentConfig",
    "DeploymentHandle", "DeploymentResponse",
    "DeploymentStreamingResponse", "HTTPOptions", "batch",
    "delete", "deployment", "get_app_handle", "get_deployment_handle",
    "get_multiplexed_model_id", "multiplexed", "run", "shutdown", "start",
    "status",
]
