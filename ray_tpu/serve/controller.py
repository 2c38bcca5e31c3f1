"""The Serve controller actor: reconciles deployment target state.

Reference: python/ray/serve/_private/controller.py (ServeController :91)
+ deployment_state.py (DeploymentStateManager :2366, DeploymentState
:1221): the controller holds the *target* state (deployments × replica
counts), a reconcile loop starts/stops replica actors toward it, health
checks demote failed replicas, and the autoscaler adjusts targets from
replica queue metrics. Membership changes fan out to routers via
long-poll (long_poll.py).
"""

from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any

from ray_tpu.serve.config import DeploymentConfig, ReplicaConfig
from ray_tpu.serve.long_poll import LongPollHost

RECONCILE_PERIOD_S = 0.05


@dataclass
class _ReplicaState:
    tag: str
    handle: Any
    healthy: bool = True
    last_ongoing: float = 0.0
    # In-flight health probe: (ref, sent_at monotonic). A probe
    # unanswered past health_check_timeout_s marks the replica dead.
    probe: tuple | None = None


@dataclass
class _DeploymentState:
    app_name: str
    name: str
    deployment_config: DeploymentConfig
    replica_config: ReplicaConfig
    target_replicas: int = 1
    replicas: list[_ReplicaState] = field(default_factory=list)
    handle_args: dict = field(default_factory=dict)
    last_scale_change: float = 0.0
    deleting: bool = False
    # Latency-driven autoscaling (AutoscalingConfig.target_p99_s > 0):
    # the freshest router-pushed latency_stats() + receipt stamp, and
    # the per-deployment LatencyPolicy instance (cooldown state).
    latency_report: dict | None = None
    latency_report_ts: float = 0.0
    latency_policy: Any = None


def _constructed(handle) -> bool:
    """The actor's constructor has returned or raised (a handle that
    does not say counts as constructed)."""
    import ray_tpu

    ref = getattr(handle, "_creation_ref", None)
    if ref is None:
        return True
    try:
        ready, _ = ray_tpu.wait([ref], timeout=0)
    except Exception:  # noqa: BLE001 — the probe will say what is wrong
        return True
    return bool(ready)


class ServeController:
    """Runs as a named actor; methods are the control-plane API."""

    def __init__(self):
        self._lock = threading.RLock()
        self._deployments: dict[tuple[str, str], _DeploymentState] = {}
        self._ingress: dict[str, str] = {}
        self._long_poll = LongPollHost()
        self._replica_counter = itertools.count()
        self._shutdown = threading.Event()
        self._loop_thread = threading.Thread(
            target=self._reconcile_loop, name="serve-controller", daemon=True)
        self._loop_thread.start()

    # -------------------------------------------------------------- deploy

    def deploy(self, app_name: str, name: str,
               deployment_config: DeploymentConfig,
               replica_config: ReplicaConfig,
               handle_args: dict | None = None) -> None:
        with self._lock:
            key = (app_name, name)
            state = self._deployments.get(key)
            if state is None:
                state = _DeploymentState(
                    app_name=app_name, name=name,
                    deployment_config=deployment_config,
                    replica_config=replica_config,
                    handle_args=handle_args or {})
                self._deployments[key] = state
            else:
                state.deployment_config = deployment_config
                state.replica_config = replica_config
                state.handle_args = handle_args or {}
                state.deleting = False
                # In-place reconfigure of live replicas on user_config
                # change (reference: DeploymentState autoscaling +
                # reconfigure broadcast).
                if deployment_config.user_config is not None:
                    for replica in state.replicas:
                        replica.handle.reconfigure.remote(
                            deployment_config.user_config)
            state.target_replicas = deployment_config.target_num_replicas

    def get_max_queued(self, app_name: str, name: str) -> int:
        """Router-side shedding limit for one deployment
        (DeploymentConfig.max_queued_requests; -1 = unlimited)."""
        with self._lock:
            state = self._deployments.get((app_name, name))
            if state is None:
                return -1
            return int(getattr(state.deployment_config,
                               "max_queued_requests", -1))

    def report_latency(self, app_name: str, name: str,
                       stats: dict) -> None:
        """Router push: the live per-deployment latency summary
        (count/mean/p50_s/p99_s) the latency autoscaler consumes.
        Routers live in every handle-holding process; last writer wins
        — the policy only needs A fresh view, not a merged one."""
        with self._lock:
            state = self._deployments.get((app_name, name))
            if state is not None:
                state.latency_report = dict(stats or {})
                state.latency_report_ts = time.monotonic()

    def get_latency_report(self, app_name: str, name: str) -> dict:
        """The freshest pushed report + its age (tests/debugging)."""
        with self._lock:
            state = self._deployments.get((app_name, name))
            if state is None or state.latency_report is None:
                return {}
            return {**state.latency_report,
                    "age_s": time.monotonic() - state.latency_report_ts}

    def set_ingress(self, app_name: str, deployment_name: str) -> None:
        with self._lock:
            self._ingress[app_name] = deployment_name

    def get_ingress(self, app_name: str) -> str | None:
        with self._lock:
            return self._ingress.get(app_name)

    def delete_app(self, app_name: str) -> None:
        with self._lock:
            self._ingress.pop(app_name, None)
            for key, state in self._deployments.items():
                if key[0] == app_name:
                    state.deleting = True
                    state.target_replicas = 0

    def shutdown(self) -> None:
        with self._lock:
            self._ingress.clear()
            for state in self._deployments.values():
                state.deleting = True
                state.target_replicas = 0
        self._shutdown.set()

    # -------------------------------------------------------------- queries

    def listen_for_change(self, keys_to_versions: dict):
        return self._long_poll.listen_for_change(keys_to_versions)

    def get_status(self) -> dict:
        with self._lock:
            return {
                f"{app}::{name}": {
                    "target_replicas": st.target_replicas,
                    "running_replicas": len(st.replicas),
                    "replica_tags": [r.tag for r in st.replicas],
                }
                for (app, name), st in self._deployments.items()
                if not st.deleting
            }

    def list_deployments(self) -> list[tuple[str, str]]:
        with self._lock:
            return [key for key, st in self._deployments.items()
                    if not st.deleting]

    # ------------------------------------------------------------ reconcile

    def _start_replica(self, state: _DeploymentState) -> None:
        import ray_tpu
        from ray_tpu.serve.replica import Replica

        tag = f"{state.name}#{next(self._replica_counter)}"
        opts = dict(state.replica_config.ray_actor_options or {})
        opts.setdefault("max_concurrency", 16)
        cfg = state.deployment_config
        handle = ray_tpu.remote(Replica).options(**opts).remote(
            state.name, tag,
            state.replica_config.deployment_def,
            state.replica_config.init_args,
            state.replica_config.init_kwargs,
            user_config=cfg.user_config,
            max_ongoing_requests=cfg.max_ongoing_requests,
            handle_args=state.handle_args,
        )
        state.replicas.append(_ReplicaState(tag=tag, handle=handle))

    def _stop_replica(self, replica: _ReplicaState,
                      graceful_timeout_s: float = 5.0) -> None:
        import ray_tpu

        def drain_then_kill():
            try:
                ref = replica.handle.prepare_for_shutdown.remote()
                ray_tpu.get(ref, timeout=graceful_timeout_s)
            except Exception:  # noqa: BLE001 — drain is best-effort
                pass
            try:
                ray_tpu.kill(replica.handle, no_restart=True)
            except Exception:  # noqa: BLE001 — already dead is fine
                pass

        # Off the reconcile thread: the graceful drain must not stall
        # reconciliation of other deployments.
        threading.Thread(target=drain_then_kill, daemon=True,
                         name=f"stop-{replica.tag}").start()

    def _broadcast(self, state: _DeploymentState) -> None:
        key = f"replicas::{state.app_name}::{state.name}"
        self._long_poll.notify_changed(
            key, [r.handle for r in state.replicas if r.healthy])

    def _reconcile_once(self) -> None:
        import ray_tpu

        with self._lock:
            states = list(self._deployments.items())
        for key, state in states:
            with self._lock:
                changed = False
                while len(state.replicas) < state.target_replicas:
                    self._start_replica(state)
                    changed = True
                while len(state.replicas) > state.target_replicas:
                    self._stop_replica(
                        state.replicas.pop(),
                        state.deployment_config.graceful_shutdown_timeout_s)
                    changed = True
                if changed:
                    state.last_scale_change = time.monotonic()
                    self._broadcast(state)
                if state.deleting and not state.replicas:
                    del self._deployments[key]

    def _autoscale_once(self) -> None:
        import ray_tpu

        with self._lock:
            states = [st for st in self._deployments.values()
                      if st.deployment_config.autoscaling_config is not None
                      and not st.deleting]
        for state in states:
            cfg = state.deployment_config.autoscaling_config
            refs = []
            with self._lock:
                replicas = list(state.replicas)
            for replica in replicas:
                try:
                    refs.append(replica.handle.get_metrics.remote())
                except Exception:  # noqa: BLE001
                    pass
            total_ongoing = 0.0
            engine_depth = 0.0
            for ref in refs:
                try:
                    metrics = ray_tpu.get(ref, timeout=1.0)
                    total_ongoing += metrics["num_ongoing_requests"]
                    # Engine-hosting replicas (LLM) report their
                    # INTERNAL queue too — requests parked in the
                    # engine's waiting queue are invisible to the
                    # replica's ongoing count but are exactly the load
                    # the autoscaler must see.
                    engine_depth += float(
                        metrics.get("engine_depth", 0) or 0)
                except Exception:  # noqa: BLE001 — dead replica
                    pass
            current = len(replicas)
            now = time.monotonic()
            if getattr(cfg, "target_p99_s", 0.0) > 0:
                desired = self._latency_desired(
                    state, cfg, current, total_ongoing + engine_depth,
                    now)
                if desired is not None and desired != current:
                    with self._lock:
                        state.target_replicas = desired
                continue
            desired = cfg.desired_replicas(
                total_ongoing + engine_depth, current)
            delay = (cfg.upscale_delay_s if desired > current
                     else cfg.downscale_delay_s)
            if desired != current and \
                    now - state.last_scale_change >= delay:
                with self._lock:
                    state.target_replicas = desired

    def _latency_desired(self, state: _DeploymentState, cfg,
                         current: int, depth: float,
                         now: float) -> "int | None":
        """The latency-driven closed loop: LatencyPolicy over the
        freshest router-pushed p99 plus engine/replica depth."""
        from ray_tpu.serve.llm_engine.autoscale import LatencyPolicy

        with self._lock:
            if state.latency_policy is None:
                state.latency_policy = LatencyPolicy(cfg)
            policy = state.latency_policy
            report = state.latency_report
            age_s = (now - state.latency_report_ts
                     if report is not None else float("inf"))
        if report is None or current == 0:
            return None
        return policy.desired(current, float(report.get("p99_s", 0.0)),
                              depth, now, feed_age_s=age_s)

    def _health_check_once(self) -> None:
        """Fully non-blocking probe cycle: each replica carries at most
        one outstanding check_health ref; a probe that raises → dead, a
        probe unanswered past health_check_timeout_s → dead (hung
        replica), otherwise keep waiting. A slow replica never stalls
        the reconcile thread. A replica is first probed when its
        constructor has returned or raised: a constructor is
        initialisation, not a hung call, and one that builds a model
        outlasts the timeout on a healthy replica (compiling the
        weights' initialisation from an empty cache took over 30 s on
        the v5e, PR 33: the replica was killed and replaced, over and
        over, each replacement starting the same compilation again)."""
        import ray_tpu

        with self._lock:
            states = list(self._deployments.values())
        now = time.monotonic()
        for state in states:
            timeout_s = state.deployment_config.health_check_timeout_s
            dead = []
            with self._lock:
                replicas = list(state.replicas)
            for replica in replicas:
                if replica.probe is None:
                    if not _constructed(replica.handle):
                        continue
                    try:
                        replica.probe = (
                            replica.handle.check_health.remote(), now)
                    except Exception:  # noqa: BLE001 — clearly dead
                        dead.append(replica)
                    continue
                ref, sent_at = replica.probe
                try:
                    ready, _ = ray_tpu.wait([ref], timeout=0)
                except Exception:  # noqa: BLE001
                    ready = [ref]
                if ready:
                    try:
                        ray_tpu.get(ref, timeout=1.0)
                        replica.probe = None  # healthy; next tick re-probes
                    except Exception:  # noqa: BLE001 — probe raised
                        dead.append(replica)
                elif now - sent_at > timeout_s:
                    dead.append(replica)  # hung past the deadline
            if dead:
                with self._lock:
                    for replica in dead:
                        if replica in state.replicas:
                            state.replicas.remove(replica)
                            self._stop_replica(
                                replica, state.deployment_config
                                .graceful_shutdown_timeout_s)
                    self._broadcast(state)  # replacements come next tick

    def _reconcile_loop(self) -> None:
        last_autoscale = 0.0
        last_health = 0.0
        while not self._shutdown.is_set():
            try:
                self._reconcile_once()
                now = time.monotonic()
                if now - last_autoscale > 0.25:
                    self._autoscale_once()
                    last_autoscale = now
                with self._lock:
                    period = min(
                        (st.deployment_config.health_check_period_s
                         for st in self._deployments.values()),
                        default=2.0)
                if now - last_health > period:
                    self._health_check_once()
                    last_health = now
            except Exception:  # noqa: BLE001 — keep the loop alive
                pass
            time.sleep(RECONCILE_PERIOD_S)
        # Drain on shutdown.
        try:
            self._reconcile_once()
        except Exception:  # noqa: BLE001
            pass
