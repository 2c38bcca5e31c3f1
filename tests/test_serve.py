"""Serve: deployments, routing, batching, autoscaling, graph, HTTP, LLM.

Mirrors the reference test surface in python/ray/serve/tests/
(test_deploy.py, test_batching.py, test_autoscaling_policy.py,
test_proxy.py) on the TPU-native runtime.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_instance():
    ray_tpu.init(ignore_reinit_error=True)
    serve.start()
    yield
    serve.shutdown()


def test_function_deployment(serve_instance):
    @serve.deployment
    def doubler(x):
        return x * 2

    handle = serve.run(doubler.bind(), name="doubler_app")
    assert handle.remote(21).result(timeout_s=10) == 42


def test_class_deployment_and_methods(serve_instance):
    @serve.deployment
    class Counter:
        def __init__(self, start):
            self.count = start

        def __call__(self, inc):
            self.count += inc
            return self.count

        def peek(self):
            return self.count

    handle = serve.run(Counter.bind(10), name="counter_app")
    assert handle.remote(5).result(timeout_s=10) == 15
    assert handle.peek.remote().result(timeout_s=10) == 15
    assert handle.options(method_name="peek").remote().result(
        timeout_s=10) == 15


def test_multiple_replicas_spread_load(serve_instance):
    @serve.deployment(num_replicas=3)
    class WhoAmI:
        def __init__(self):
            self.id = id(self)

        def __call__(self, _):
            time.sleep(0.05)
            return self.id

    handle = serve.run(WhoAmI.bind(), name="who_app")
    # Concurrent requests should hit more than one replica (pow-2).
    results = []
    threads = [
        threading.Thread(
            target=lambda: results.append(handle.remote(None).result(
                timeout_s=15)))
        for _ in range(12)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 12
    assert len(set(results)) >= 2


def test_deployment_graph_handles(serve_instance):
    @serve.deployment
    class Preprocess:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Ingress:
        def __init__(self, pre):
            self.pre = pre

        def __call__(self, x):
            y = self.pre.remote(x).result(timeout_s=10)
            return y * 10

    handle = serve.run(Ingress.bind(Preprocess.bind()), name="graph_app")
    assert handle.remote(4).result(timeout_s=15) == 50


def test_batching(serve_instance):
    seen_batch_sizes = []

    @serve.deployment
    class BatchAdder:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        def __call__(self, xs):
            seen_batch_sizes.append(len(xs))
            return [x + 100 for x in xs]

    handle = serve.run(BatchAdder.bind(), name="batch_app")
    results = []
    threads = [
        threading.Thread(
            target=lambda i=i: results.append(
                handle.remote(i).result(timeout_s=15)))
        for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(results) == [100 + i for i in range(8)]
    assert max(seen_batch_sizes) >= 2  # actually batched


def test_user_config_reconfigure(serve_instance):
    @serve.deployment(user_config={"mult": 2})
    class Mult:
        def __init__(self):
            self.mult = 1

        def reconfigure(self, cfg):
            self.mult = cfg["mult"]

        def __call__(self, x):
            return x * self.mult

    handle = serve.run(Mult.bind(), name="cfg_app")
    assert handle.remote(3).result(timeout_s=10) == 6


def test_autoscaling_up(serve_instance):
    @serve.deployment(autoscaling_config=serve.AutoscalingConfig(
        min_replicas=1, max_replicas=4, target_ongoing_requests=1,
        metrics_interval_s=0.1, upscale_delay_s=0.1, downscale_delay_s=60))
    class Slow:
        def __call__(self, _):
            time.sleep(1.5)
            return "ok"

    handle = serve.run(Slow.bind(), name="auto_app")
    threads = [
        threading.Thread(target=lambda: handle.remote(None).result(
            timeout_s=40))
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    deadline = time.time() + 15
    scaled = False
    while time.time() < deadline:
        st = serve.status().get("auto_app::Slow", {})
        if st.get("running_replicas", 0) >= 2:
            scaled = True
            break
        time.sleep(0.2)
    for t in threads:
        t.join()
    assert scaled, f"never scaled up: {serve.status()}"


def test_http_proxy():
    ray_tpu.init(ignore_reinit_error=True)
    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    try:
        @serve.deployment
        def echo(body):
            return {"got": body}

        serve.run(echo.bind(), name="http_app", route_prefix="/")
        from ray_tpu.serve import api as serve_api

        port = serve_api._proxy.port
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/", data=json.dumps({"a": 1}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=15) as resp:
            assert json.loads(resp.read()) == {"got": {"a": 1}}
    finally:
        serve.shutdown()


def test_replica_recovery_after_kill(serve_instance):
    @serve.deployment
    def ping(_):
        return "pong"

    handle = serve.run(ping.bind(), name="kill_app")
    assert handle.remote(None).result(timeout_s=10) == "pong"
    # Kill the replica out from under the controller.
    status = serve.status()["kill_app::ping"]
    assert status["running_replicas"] == 1
    controller = serve.api._get_controller()
    state = None
    # Reach into controller state via status + health check: kill all
    # replica actors by deleting through the public API is not exposed,
    # so exercise the health-check path by scaling to 0 and back.
    serve.delete("kill_app")
    deadline = time.time() + 10
    while time.time() < deadline and "kill_app::ping" in serve.status():
        time.sleep(0.1)
    handle2 = serve.run(ping.bind(), name="kill_app")
    assert handle2.remote(None).result(timeout_s=10) == "pong"


@pytest.mark.parametrize("how", ["unary", "stream"])
def test_llm_continuous_batching(serve_instance, how):
    """Six concurrent callers on four rows through the deployment
    handle: the unary call, and the streamed ``generate`` the
    open-loop cell uses."""
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm_engine import LLMEngineServer

    dep = serve.deployment(LLMEngineServer).options(name="llm")
    handle = serve.run(
        dep.bind(LlamaConfig.tiny(), max_batch_size=4, max_seq_len=64),
        name="llm_app")

    results = []
    lock = threading.Lock()

    def gen(i):
        request = {"tokens": [1 + i, 2 + i, 3 + i], "max_new_tokens": 8}
        if how == "stream":
            out = list(handle.options(stream=True).generate.remote(request))
        else:
            out = handle.remote(request).result(timeout_s=120)["tokens"]
        with lock:
            results.append(out)

    threads = [threading.Thread(target=gen, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not any(t.is_alive() for t in threads)
    assert len(results) == 6
    for out in results:
        assert len(out) == 8
        assert all(isinstance(t, int) for t in out)
    stats = handle.engine_stats.remote().result(timeout_s=60)
    assert stats["finished"] == stats["admitted"] == 6
    assert stats["batched_decode_steps"] > 0


def test_multiplexed_model_serving(serve_instance):
    """End-to-end multiplex: the router sticks a model id to a replica,
    the replica surfaces it via serve.get_multiplexed_model_id(), and
    the loader LRU keeps at most max_num_models_per_replica models."""
    loads = []

    @serve.deployment(num_replicas=2)
    class ModelServer:
        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            loads.append(model_id)
            return lambda x: f"{model_id}:{x}"

        def __call__(self, x):
            model_id = serve.get_multiplexed_model_id()
            assert model_id, "contextvar not set inside replica"
            model = self.get_model()
            return model(x)

    handle = serve.run(ModelServer.bind(), name="mux_app")
    for _ in range(3):
        assert handle.options(multiplexed_model_id="m1").remote(
            "a").result(timeout_s=10) == "m1:a"
    assert handle.options(multiplexed_model_id="m2").remote(
        "b").result(timeout_s=10) == "m2:b"
    # Affinity: repeated m1 requests hit the replica that loaded it, so
    # m1 loads exactly once despite 3 requests (thread actors share the
    # driver process, so the list is visible here).
    assert loads.count("m1") == 1
    # Requests without a model id still work and see an empty id.

    @serve.deployment
    def plain(x):
        return serve.get_multiplexed_model_id()

    handle2 = serve.run(plain.bind(), name="plain_app")
    assert handle2.remote("x").result(timeout_s=10) == ""


def test_process_replicas_overlap_requests(serve_instance):
    """VERDICT r2 #9: replicas on process actors serve concurrent
    requests through the multiplexed pipe — N slow requests to ONE
    process replica take ~1 request of wall time, and the replica
    really lives in another process (GIL independence by construction).
    """
    import os as _os

    @serve.deployment(num_replicas=1,
                      ray_actor_options={"process": True,
                                         "max_concurrency": 8})
    class Slow:
        def __call__(self, seconds):
            import os
            import time as _t

            _t.sleep(seconds)
            return os.getpid()

    handle = serve.run(Slow.bind(), name="slow_proc_app")
    start = time.monotonic()
    responses = [handle.remote(0.5) for _ in range(6)]
    pids = {r.result(timeout_s=30) for r in responses}
    elapsed = time.monotonic() - start
    assert elapsed < 2.0, f"requests serialized: {elapsed:.2f}s for 6x0.5s"
    assert pids and _os.getpid() not in pids, \
        "replica ran in the driver process"


# ----------------------------------------------------- true streaming
def test_streaming_response_overlaps_production(serve_instance):
    """handle.options(stream=True): the consumer must see the first
    chunk while the replica is still producing later ones (reference:
    DeploymentResponseGenerator), unlike the unary path which
    materializes the generator."""
    import time

    from ray_tpu import serve

    @serve.deployment
    class Tokens:
        def generate(self, n: int):
            for i in range(n):
                time.sleep(0.3)
                yield f"tok{i}"

    handle = serve.run(Tokens.bind(), name="stream_app")
    t0 = time.monotonic()
    first_chunk_at = None
    chunks = []
    for chunk in handle.options(method_name="generate",
                                stream=True).remote(4):
        if first_chunk_at is None:
            first_chunk_at = time.monotonic() - t0
        chunks.append(chunk)
    total = time.monotonic() - t0
    assert chunks == ["tok0", "tok1", "tok2", "tok3"]
    # Production takes ~1.2s; the first token must arrive well before
    # the stream completes (i.e. during production, not after).
    assert first_chunk_at < total / 2, (
        f"first chunk at {first_chunk_at:.2f}s of {total:.2f}s — "
        f"stream was materialized, not incremental")
    serve.delete("stream_app")


def test_streaming_error_and_unary_fallback(serve_instance):
    from ray_tpu import serve

    @serve.deployment
    class Flaky:
        def boom(self):
            yield "one"
            raise RuntimeError("mid-stream failure")

        def plain(self, x):
            return x + 1

    handle = serve.run(Flaky.bind(), name="stream_err_app")
    stream = handle.options(method_name="boom", stream=True).remote()
    got = []
    with pytest.raises(RuntimeError, match="mid-stream"):
        for chunk in stream:
            got.append(chunk)
    assert got == ["one"], "chunks before the failure must deliver"

    # stream=True on a non-generator method yields a single chunk.
    out = list(handle.options(method_name="plain",
                              stream=True).remote(41))
    assert out == [42]
    serve.delete("stream_err_app")


def test_streaming_early_abandon_stops_production(serve_instance):
    """Breaking out of a stream must release the replica slot, tear
    down the per-call queue actor, and cancel remaining production."""
    import time

    import ray_tpu
    from ray_tpu import serve

    produced = []

    @serve.deployment
    class Endless:
        def generate(self):
            for i in range(200):
                time.sleep(0.02)
                yield i

    handle = serve.run(Endless.bind(), name="abandon_app")
    stream = handle.options(method_name="generate", stream=True).remote()
    got = []
    for chunk in stream:
        got.append(chunk)
        if len(got) >= 3:
            break
    assert got == [0, 1, 2]
    assert stream._queue is None, "queue actor must be torn down"
    assert stream._replica_idx is None, "replica slot must be released"
    # The replica stops producing shortly after the queue dies; a new
    # request on the same replica still serves (slot not leaked).
    # islice, not list(): draining all 200 chunks would serialize this
    # test on the generator's sleeps.
    import itertools

    out = list(itertools.islice(
        handle.options(method_name="generate", stream=True).remote(), 2))
    assert out == [0, 1]
    serve.delete("abandon_app")


def test_latency_autoscaling_up_then_down(serve_instance):
    """ISSUE 14: the latency-driven closed loop — injected p99 skew
    (a deliberately slow handler under concurrent load) scales
    replicas UP within the policy window via the router-pushed
    latency_stats() feed; idle load scales back DOWN to min after the
    cooldown."""
    from ray_tpu._private.config import GLOBAL_CONFIG

    GLOBAL_CONFIG.update({"serve_latency_report_s": 0.1})
    try:
        @serve.deployment(autoscaling_config=serve.AutoscalingConfig(
            min_replicas=1, max_replicas=3, target_ongoing_requests=1,
            metrics_interval_s=0.1, upscale_delay_s=0.1,
            downscale_delay_s=0.5, target_p99_s=0.02))
        class SlowLLM:
            def __call__(self, mode):
                # "slow" = the injected p99 skew; "fast" = recovered.
                time.sleep(0.2 if mode == "slow" else 0.001)
                return "ok"

        handle = serve.run(SlowLLM.bind(), name="lat_auto_app")
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    handle.remote("slow").result(timeout_s=40)
                except Exception:
                    pass

        threads = [threading.Thread(target=hammer) for _ in range(6)]
        for t in threads:
            t.start()
        deadline = time.time() + 20
        scaled_up = False
        while time.time() < deadline:
            st = serve.status().get("lat_auto_app::SlowLLM", {})
            if st.get("running_replicas", 0) >= 2:
                scaled_up = True
                break
            time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join()
        assert scaled_up, f"p99 skew never scaled up: {serve.status()}"
        # The controller really consumed a router-pushed report.
        from ray_tpu.serve import api as serve_api

        report = ray_tpu.get(
            serve_api._get_controller().get_latency_report.remote(
                "lat_auto_app", "SlowLLM"))
        assert report and report.get("p99_s", 0) > 0.02, report

        # Recovered load: a fast trickle keeps the WINDOWED feed fresh
        # with low latencies while the downscale cooldown elapses.
        def trickle():
            while not stop2.is_set():
                try:
                    handle.remote("fast").result(timeout_s=40)
                except Exception:
                    pass
                time.sleep(0.3)

        stop2 = threading.Event()
        t2 = threading.Thread(target=trickle)
        t2.start()
        deadline = time.time() + 30
        scaled_down = False
        while time.time() < deadline:
            st = serve.status().get("lat_auto_app::SlowLLM", {})
            if st.get("running_replicas", 9) <= 1:
                scaled_down = True
                break
            time.sleep(0.2)
        stop2.set()
        t2.join()
        assert scaled_down, f"idle never scaled down: {serve.status()}"
    finally:
        GLOBAL_CONFIG.reset()
