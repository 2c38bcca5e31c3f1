"""Serve's health probe: a replica is probed once its constructor is
done, and replaced when a probe then goes unanswered."""

import os
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_instance():
    ray_tpu.init(ignore_reinit_error=True)
    serve.start()
    yield
    serve.shutdown()


def _impatient(deployment):
    """The deployment probed every 0.1 s, a probe given 1 s."""
    deployment = deployment.options(health_check_period_s=0.1)
    deployment.deployment_config.health_check_timeout_s = 1.0
    return deployment


def _constructions(path) -> int:
    return len(os.listdir(path))


@pytest.mark.parametrize("hangs_when_probed", [False, True],
                         ids=["slow_constructor", "hung_probe"])
def test_probe_waits_for_the_constructor(serve_instance, tmp_path,
                                         hangs_when_probed):
    """A constructor three times as long as the probe's timeout is
    initialisation: the replica is neither killed nor built a second
    time (a model's weights compile for longer than a probe may take).
    Once built, a replica that leaves a probe unanswered is replaced."""
    built = str(tmp_path)

    @serve.deployment
    class Slow:
        def __init__(self):
            open(os.path.join(built, f"{time.monotonic_ns()}"), "w").close()
            self.first = _constructions(built) == 1
            time.sleep(3.0)

        def check_health(self):
            if hangs_when_probed and self.first:
                time.sleep(60.0)

        def __call__(self):
            return "served"

    handle = serve.run(_impatient(Slow).bind(),
                       name=f"slow_{hangs_when_probed}")
    assert handle.remote().result(timeout_s=30) == "served"
    if not hangs_when_probed:
        time.sleep(2.5)  # probes come and go; the one replica stays
        assert _constructions(built) == 1
        assert handle.remote().result(timeout_s=30) == "served"
        return
    deadline = time.monotonic() + 30
    while _constructions(built) < 2 and time.monotonic() < deadline:
        time.sleep(0.1)
    assert _constructions(built) == 2  # the hung replica was replaced
    assert handle.remote().result(timeout_s=30) == "served"
