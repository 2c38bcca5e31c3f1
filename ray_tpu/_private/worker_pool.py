"""Multiprocess worker pool: OS-process task execution + process actors.

TPU-native analogue of the reference's worker pool + direct task
transport (src/ray/raylet/worker_pool.h forks language workers;
src/ray/core_worker/transport/direct_task_transport.h:75 pushes tasks to
leased workers): the driver spawns N Python worker processes, pushes
tasks over a duplex pipe with a cloudpickle serialization boundary, and
moves data through named shared-memory segments (shm_store.py) so
worker-to-worker arguments never copy through the driver.

Why processes: the thread-worker slice shares one GIL — CPU-bound
fan-out (RLlib rollouts, data preprocessing) cannot exceed one core.
Pool workers are real processes; a crashed worker is detected by pipe
EOF, the task fails with WorkerCrashedError (retryable as a system
failure, like the reference's worker-death retries), and the pool
respawns the worker.

Process actors (``ProcessActor``) give an actor a dedicated worker
process: constructor and method calls execute there in submission
order; max_restarts respawns the process and re-runs the constructor.

Nested submission: code inside a pool worker (tasks and process actors)
can call the full public API — remote()/get()/put()/wait()/actors — via
a proxy runtime that routes to the driver's client server
(worker_client.py). Blocked nested gets from TASKS release the owning
task's CPU admission through a task token, and the pool grows on demand
(up to max_size) so an outer task waiting on an inner one never starves
it. Process-actor calls carry no token — actors hold their resources
for their lifetime (and default to 0 CPU, like the reference), so
blocked actor gets keep their lease.

Process actors honor ``max_concurrency``: above 1 the pipe switches to
a multiplexed protocol (calls tagged with ids, a worker-side thread
pool, interleaved replies), so e.g. serve replicas on process actors
overlap requests AND scale past one GIL (reference: actor concurrency
groups, transport/concurrency_group_manager.h).
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from dataclasses import dataclass
from typing import Any, Callable

from ray_tpu._private import perf_plane as perf
from ray_tpu._private import serialization
from ray_tpu._private.ids import ActorID, ObjectID
from ray_tpu._private.shm_store import (
    ArenaDescriptor,
    ShmClient,
    ShmDescriptor,
    ShmDirectory,
    ShmObjectWriter,
    untrack,
)
from ray_tpu.exceptions import (
    ActorDiedError,
    ActorError,
    TaskError,
    WorkerCrashedError,
)

# Results smaller than worker_inline_result_kb (config) ship inline
# through the pipe; mid-size ones go through the native shared arena
# (one lock round-trip, no syscalls); larger ones get a dedicated
# shared-memory segment the driver adopts (true zero-copy reads). The
# arena cutoff comes from config (object_arena_max_object_bytes) via
# the RAY_TPU_ARENA_MAX env var.


def _inline_result_bytes() -> int:
    from ray_tpu._private.config import GLOBAL_CONFIG

    return int(GLOBAL_CONFIG.worker_inline_result_kb) * 1024


@dataclass
class _ShmRef:
    """Placeholder for an ObjectRef argument: resolved worker-side by
    mapping the segment (zero-copy)."""

    desc: ShmDescriptor


@dataclass
class _BatchTask:
    """One task of a pipelined batch run (WorkerPool.run_task_batch)."""

    idx: int                    # caller's position in the batch
    digest: str
    func_blob: bytes | None     # resolved by the caller (never None
    args_blob: bytes            # unless the worker already knows digest)
    n_returns: int
    runtime_env: dict | None = None
    token: str | None = None
    client_addr: str | None = None
    sys_path: list | None = None
    # Driver trace context (trace_id, parent span_id, anchor): rides the
    # task_seq frame so the worker stamps frame/exec times and the reply
    # carries them back. None ⇒ tracing off for this task (zero cost).
    trace: tuple | None = None
    # Absolute end-to-end deadline: rides the task_seq frame so the
    # worker refuses frames whose budget died queued behind the lease
    # head (reply status "timeout" — nothing executed).
    deadline: float | None = None
    # Driver over-subscribed this entry past the node's free slots
    # (entry flags bit 2): a failed reservation PARKS it in daemon
    # admission instead of bouncing a ("busy",) spillback.
    overcommit: bool = False
    # Return-object keys, needed daemon-side by the fused in-daemon
    # path (the worker path resolves them from the batch entries).
    return_keys: list | None = None


# --------------------------------------------------------------------------
# Worker process side
# --------------------------------------------------------------------------


def _exception_blob(exc: BaseException) -> bytes:
    tb = "".join(traceback.format_exception(type(exc), exc, exc.__traceback__))
    try:
        return serialization.serialize_framed((exc, tb))
    except Exception:
        return serialization.serialize_framed(
            (RuntimeError(f"{type(exc).__name__}: {exc}"), tb))


class _runtime_env_ctx:
    """Apply a runtime_env around one task execution in the worker
    process (reference: python/ray/_private/runtime_env/ — per-worker
    env_vars and working_dir; our pool workers are shared, so the env
    is applied per-task and restored after)."""

    def __init__(self, runtime_env: dict | None):
        from ray_tpu._private.runtime_env_packaging import (
            resolve_runtime_env,
        )

        # Package markers ({"__pkg__": [hash, addr]}) become locally
        # extracted directories here (downloaded once per node, cached).
        self.env = resolve_runtime_env(runtime_env) or {}
        self._saved_vars: dict[str, str | None] = {}
        self._saved_cwd: str | None = None
        self._added_sys_paths: list[str] = []
        self._unload_prefixes: list[str] = []

    def _push_site(self, site: str) -> None:
        if site not in sys.path:
            sys.path.insert(0, site)
            self._added_sys_paths.append(site)
        self._unload_prefixes.append(site)

    def __enter__(self):
        try:
            self._enter_impl()
        except BaseException:
            # Partial application must not leak into the next task on
            # this shared worker (e.g. env_vars applied, then pip
            # failed): roll back what was done, then surface the error.
            self.__exit__(None, None, None)
            raise
        return self

    def _enter_impl(self):
        # Env backends FIRST (they can fail — a venv/conda error must
        # abort before any os.environ mutation): a per-hash env created
        # once per node and cached; its site-packages is prepended for
        # this task's duration and its modules unloaded after
        # (reference: runtime_env/{pip,conda}.py).
        pip_spec = self.env.get("pip")
        conda_spec = self.env.get("conda")
        if pip_spec and conda_spec:
            # Ambiguous layering (whose site-packages wins?); the
            # reference rejects the combination too. Nested pip deps
            # belong INSIDE the conda spec's dependencies.
            raise ValueError(
                "runtime_env cannot specify both 'pip' and 'conda'; "
                "put pip packages in the conda spec's dependencies "
                "({'conda': {'dependencies': [{'pip': [...]}]}})")
        if pip_spec:
            from ray_tpu._private.runtime_env_pip import ensure_pip_env

            self._push_site(ensure_pip_env(pip_spec)["site_packages"])
        if conda_spec:
            from ray_tpu._private.runtime_env_conda import (
                ensure_conda_env,
            )

            self._push_site(ensure_conda_env(conda_spec)["site_packages"])
        for k, v in (self.env.get("env_vars") or {}).items():
            self._saved_vars[k] = os.environ.get(k)
            os.environ[k] = str(v)
        working_dir = self.env.get("working_dir")
        if working_dir:
            self._saved_cwd = os.getcwd()
            os.chdir(working_dir)
            if working_dir not in sys.path:
                sys.path.insert(0, working_dir)
                self._added_sys_paths.append(working_dir)
            self._unload_prefixes.append(os.path.abspath(working_dir))
        # py_modules: local module dirs importable task-side
        # (reference: runtime_env/py_modules.py; local paths only —
        # no URI packaging without a cluster-wide store).
        for path in (self.env.get("py_modules") or []):
            abspath = os.path.abspath(path)
            parent = os.path.dirname(abspath)
            if parent not in sys.path:
                sys.path.insert(0, parent)
                self._added_sys_paths.append(parent)
            # Unload only the MODULE itself on exit, never the whole
            # parent directory (siblings may be imported legitimately
            # through other sys.path entries).
            self._unload_prefixes.append(abspath)

    def __exit__(self, *exc):
        if self._saved_cwd is not None:
            try:
                os.chdir(self._saved_cwd)
            except OSError:
                pass  # saved cwd may have been deleted
        if self._unload_prefixes:
            # Unload modules imported from the env's paths: pool
            # workers are shared across tasks, and a module cached in
            # sys.modules would leak into tasks without this env
            # (reference isolates via dedicated worker processes).
            dir_prefixes = tuple(p + os.sep for p in
                                 self._unload_prefixes)
            exact_files = set(self._unload_prefixes)
            for name, mod in list(sys.modules.items()):
                mod_file = getattr(mod, "__file__", None)
                if mod_file and (mod_file.startswith(dir_prefixes)
                                 or mod_file in exact_files):
                    sys.modules.pop(name, None)
        for added in self._added_sys_paths:
            try:
                sys.path.remove(added)
            except ValueError:
                pass
        for k, old in self._saved_vars.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old
        return None


def _resolve_shm_args(args, kwargs, client: ShmClient):
    args = tuple(client.get(a.desc) if isinstance(a, _ShmRef) else a
                 for a in args)
    kwargs = {k: client.get(v.desc) if isinstance(v, _ShmRef) else v
              for k, v in kwargs.items()}
    return args, kwargs


def _pack_results(values: list, arena=None, arena_max: int = 0) -> list:
    """Each value -> ("inline", bytes) | ("arena", key, size)
    | ("shm", name, size) | ("err", blob)."""
    from multiprocessing import shared_memory

    out = []
    for value in values:
        raw = serialization.try_serialize_raw(value)
        if raw is not None:
            # Small immutable result: the raw tag encoding skips the
            # pickle round trip on both ends of the pipe.
            out.append(("inline", raw))
            continue
        try:
            header, buffers = serialization.serialize(value)
        except Exception as exc:  # noqa: BLE001 — unpicklable result
            out.append(("err", _exception_blob(exc)))
            continue
        size = serialization.framed_size(header, buffers)
        if size <= _inline_result_bytes():
            blob = bytearray(size)
            serialization.write_framed(memoryview(blob), header, buffers)
            out.append(("inline", bytes(blob)))
            continue
        if arena is not None and size <= arena_max:
            key = os.urandom(16)
            view = arena.create_for_write(key, size)
            if view is not None:
                serialization.write_framed(view, header, buffers)
                # Pinned: the driver's directory inherits the reference
                # at register_arena, so the result cannot be evicted in
                # transit. (A worker crash between here and the driver
                # receiving the reply leaks this one pin — bounded by
                # crash count, and the arena dies with the driver.)
                arena.seal_pinned(key)
                out.append(("arena", key, size))
                continue
            # Arena full even after eviction: dedicated segment below.
        seg = shared_memory.SharedMemory(create=True, size=size)
        untrack(seg)  # unlink belongs to the driver directory
        serialization.write_framed(seg.buf, header, buffers)
        name = seg.name
        seg.close()  # driver adopts + unlinks; worker drops its handle
        out.append(("shm", name, size))
    return out


def worker_main(conn) -> None:
    """Worker process entry: serve task/actor requests until exit.

    The first message is ("hello", parent_sys_path): workers adopt the
    parent's sys.path so functions pickled by reference (importable
    modules, incl. test modules) resolve.
    """
    kind, parent_sys_path = conn.recv()
    assert kind == "hello", kind
    sys.path[:0] = [p for p in parent_sys_path if p not in sys.path]
    os.environ["RAY_TPU_IN_POOL_WORKER"] = "1"  # init() guard
    client = ShmClient(untrack_on_attach=True)
    # Attach the driver's shared arena (plasma-lite) when one exists.
    arena = None
    arena_name = os.environ.get("RAY_TPU_ARENA_NAME")
    if arena_name:
        from ray_tpu._private.arena_store import ArenaStore

        arena = ArenaStore.attach(arena_name)
        client.set_arena(arena)
    arena_max = int(os.environ.get("RAY_TPU_ARENA_MAX", 1024 * 1024))
    # Flight recorder: no flusher thread (workers are many and
    # short-lived) — the ring dumps only on a fatal serve-loop error,
    # and lives in memory for lifecycle records until then.
    from ray_tpu._private import flight_recorder

    flight_recorder.install("worker")
    try:
        _serve(conn, client, arena, arena_max)
    except BaseException:
        flight_recorder.record("worker.fatal")
        flight_recorder.dump("fatal")
        raise
    finally:
        client.close_all()
        if arena is not None:
            arena.close()


def _exec_task_body(fields: tuple, func_cache: dict,
                    client: ShmClient, arena, arena_max: int,
                    stages: dict | None = None) -> list:
    """Execute one task message body (the fields after the kind/call-id
    prefix) and return the packed result descriptors. Shared by the
    classic one-in-flight ``task`` protocol and the pipelined
    ``task_seq`` protocol. ``stages`` (traced frames only) receives
    exec_start/exec_end stamps around the user function call."""
    (digest, func_blob, args_blob, n_returns, renv, token) = fields[:6]
    # Daemon pools serve many drivers: the owning driver's
    # client-server address rides with each task so nested
    # API calls reach the right owner (reference: every
    # worker knows its owner's CoreWorker address).
    client_addr = fields[6] if len(fields) > 6 else None
    if len(fields) > 7 and fields[7]:
        # Driver import paths for by-reference pickles.
        sys.path.extend(p for p in fields[7]
                        if p not in sys.path)
    if func_blob is not None:
        func = serialization.loads_function(func_blob)
        func_cache[digest] = func
    else:
        func = func_cache[digest]
    args, kwargs = serialization.deserialize_from_buffer(
        memoryview(args_blob))
    args, kwargs = _resolve_shm_args(args, kwargs, client)
    # Token rides along on nested get()/wait() RPCs so the
    # driver can release this task's CPU while it blocks.
    from ray_tpu._private import worker_client

    if client_addr:
        worker_client.set_driver_addr(client_addr)
    worker_client.set_task_token(token)
    try:
        if stages is not None:
            stages["exec_start"] = time.time()
        # Always-on attribution sample (perf_plane): cpu-seconds, wall
        # and peak-RSS delta around the user function, shipped back as
        # a 4-tuple in the stages element — the daemon/driver rolls it
        # up per function signature. Gated by the SENDER (stages is
        # only created when the owning daemon/driver asked), so a
        # runtime disarm propagates to workers with the next frame.
        sample = perf.sample_start() if stages is not None else None
        with _runtime_env_ctx(renv):
            result = func(*args, **kwargs)
        if sample is not None:
            stages["perf"] = perf.sample_end(
                getattr(func, "__qualname__", digest[:8]), sample)
        if stages is not None:
            stages["exec_end"] = time.time()
    finally:
        worker_client.set_task_token(None)
    if n_returns == 0:
        values = []
    elif n_returns == 1:
        values = [result]
    else:
        if (not isinstance(result, (tuple, list))
                or len(result) != n_returns):
            raise ValueError(
                f"task declared num_returns={n_returns} but "
                f"returned {type(result).__name__}")
        values = list(result)
    return _pack_results(values, arena, arena_max)


_jax_marked = False


def _mark_jax_if_imported() -> None:
    """Tell the fork-server template when this worker pulled jax in:
    the template (two-stage boot, worker_factory.py) watches for the
    marker and preimports jax for every LATER fork. One bool check per
    message once the marker is dropped."""
    global _jax_marked
    if _jax_marked or "jax" not in sys.modules:
        return
    _jax_marked = True
    path = os.environ.get("RAY_TPU_FACTORY_MARKER")
    if not path:
        return
    try:
        with open(path, "w"):
            pass
    except OSError:
        pass  # marker touch is advisory only


def _serve(conn, client: ShmClient, arena=None,
           arena_max: int = 0) -> None:
    actor_instance = None
    func_cache: dict[str, Any] = {}
    while True:
        _mark_jax_if_imported()
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return
        kind = msg[0]
        try:
            if kind == "exit":
                return
            elif kind == "ping":
                conn.send(("pong", os.getpid()))
            elif kind == "task":
                # Optional 10th message element: the driver's trace
                # context — stamp frame pickup + exec times and return
                # them as a third reply element (same shape as the
                # pipelined task_seq protocol). The always-on perf
                # plane rides the SAME slot as the sentinel ``False``:
                # the sender's plane is armed but tracing is not, so
                # stamp pickup + the resource sample without any trace
                # machinery (None/absent ⇒ both planes off).
                slot = msg[9] if len(msg) > 9 else None
                stages = {"worker_start": time.time(),
                          "pid": os.getpid()} \
                    if slot is not None else None
                packed = _exec_task_body(
                    msg[1:], func_cache, client, arena, arena_max,
                    stages=stages)
                conn.send(("ok", packed, stages) if stages is not None
                          else ("ok", packed))
            elif kind == "task_seq":
                # Pipelined protocol: frames arrive back-to-back (the
                # sender does not wait for replies), execute serially
                # in receive order, and each reply carries its call id
                # so the daemon-side lease matches them out of order.
                # An 11th frame element is the driver's trace context:
                # stamp frame-pickup + exec times and ship them back as
                # a 5th reply element (worker and daemon share a host,
                # so these are daemon-clock timestamps).
                call_id = msg[1]
                # 11th element: trace context, or the ``False`` perf
                # sentinel (see the "task" protocol above).
                slot = msg[10] if len(msg) > 10 else None
                traced = slot is not None
                # Optional 12th element: the absolute end-to-end
                # deadline — a frame whose budget died queued behind
                # the lease head is refused, never executed.
                deadline = msg[11] if len(msg) > 11 else None
                if deadline is not None and time.time() > deadline:
                    reply = ("task_done", call_id, "timeout", None)
                    conn.send(reply + (None,) if traced else reply)
                    continue
                stages = {"worker_start": time.time(),
                          "pid": os.getpid()} \
                    if slot is not None else None
                try:
                    packed = _exec_task_body(
                        msg[2:], func_cache, client, arena, arena_max,
                        stages=stages)
                except BaseException as exc:  # noqa: BLE001 — per-task
                    reply = ("task_done", call_id, "err",
                             _exception_blob(exc))
                    conn.send(reply + (stages,)
                              if stages is not None else reply)
                else:
                    reply = ("task_done", call_id, "ok", packed)
                    if stages is not None:
                        reply = reply + (stages,)
                    conn.send(reply)
            elif kind == "actor_new":
                _, cls_blob, args_blob, renv, max_concurrency = msg[:5]
                # Remote actors: the creating driver's sys.path entries
                # (classes pickled by reference must resolve on a daemon
                # that never saw the driver's import paths; one-machine
                # clusters share the filesystem, so the paths are valid).
                if len(msg) > 5 and msg[5]:
                    sys.path.extend(p for p in msg[5]
                                    if p not in sys.path)
                cls = serialization.loads_function(cls_blob)
                args, kwargs = serialization.deserialize_from_buffer(
                    memoryview(args_blob))
                args, kwargs = _resolve_shm_args(args, kwargs, client)
                # Actor runtime_env applies for the actor's whole life:
                # this worker process is dedicated to it.
                _runtime_env_ctx(renv).__enter__()
                actor_instance = cls(*args, **kwargs)
                conn.send(("ok", None))
                if max_concurrency and max_concurrency > 1:
                    # Switch to the multiplexed protocol: calls carry
                    # ids, execute on a thread pool, and replies
                    # interleave — the serve-replica concurrency story
                    # (reference: actor concurrency groups,
                    # transport/concurrency_group_manager.h).
                    _serve_actor_concurrent(
                        conn, actor_instance, client, arena, arena_max,
                        max_concurrency)
                    return
            elif kind == "actor_call":
                _, method_name, args_blob, n_returns = msg
                if actor_instance is None:
                    raise RuntimeError("actor_call before actor_new")
                status, payload = _invoke_actor_method(
                    actor_instance, client, arena, arena_max,
                    method_name, args_blob, n_returns)
                if status == "err":
                    conn.send(("err", payload))
                else:
                    conn.send(("ok", payload))
            else:
                raise RuntimeError(f"unknown message kind {kind!r}")
        except BaseException as exc:  # noqa: BLE001 — shipped to the driver
            try:
                conn.send(("err", _exception_blob(exc)))
            except (OSError, BrokenPipeError):
                return


def _invoke_actor_method(instance, client: ShmClient, arena,
                         arena_max: int, method_name: str,
                         args_blob: bytes, n_returns: int) -> tuple:
    """Deserialize-resolve-invoke-pack, shared by the sequential and
    multiplexed serving loops. -> ("ok", packed) | ("err", blob)."""
    try:
        args, kwargs = serialization.deserialize_from_buffer(
            memoryview(args_blob))
        args, kwargs = _resolve_shm_args(args, kwargs, client)
        method = getattr(instance, method_name)
        result = method(*args, **kwargs)
        values = [result] if n_returns == 1 else \
            (list(result) if isinstance(result, (tuple, list))
             else [None] * n_returns)
        return ("ok", _pack_results(values, arena, arena_max))
    except BaseException as exc:  # noqa: BLE001 — shipped to driver
        return ("err", _exception_blob(exc))


def _serve_actor_concurrent(conn, instance, client: ShmClient, arena,
                            arena_max: int, max_concurrency: int) -> None:
    """Multiplexed actor serving: up to ``max_concurrency`` calls run
    simultaneously on a thread pool; replies are tagged with call ids
    and interleave on the pipe (send-locked)."""
    from concurrent.futures import ThreadPoolExecutor

    send_lock = threading.Lock()
    pool = ThreadPoolExecutor(max_workers=max_concurrency,
                              thread_name_prefix="actor-call")

    def run_one(call_id, method_name, args_blob, n_returns):
        status, payload = _invoke_actor_method(
            instance, client, arena, arena_max, method_name, args_blob,
            n_returns)
        try:
            with send_lock:
                conn.send(("reply", call_id, status, payload))
        except (OSError, BrokenPipeError):
            pass  # driver gone; the process is about to exit anyway

    while True:
        _mark_jax_if_imported()
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return
        kind = msg[0]
        if kind == "exit":
            pool.shutdown(wait=False, cancel_futures=True)
            return
        if kind == "ping":
            with send_lock:
                conn.send(("pong", os.getpid()))
            continue
        if kind == "actor_call_async":
            _, call_id, method_name, args_blob, n_returns = msg
            pool.submit(run_one, call_id, method_name, args_blob,
                        n_returns)
        else:
            with send_lock:
                conn.send(("reply", msg[1] if len(msg) > 1 else -1, "err",
                           _exception_blob(RuntimeError(
                               f"unknown concurrent-actor message "
                               f"{kind!r}"))))


# --------------------------------------------------------------------------
# Driver side
# --------------------------------------------------------------------------


_factory_lock = threading.Lock()
_factory = None


def _get_factory():
    """Process-global fork-server template, started on first use (and
    restarted if it died). ~1-2s once, then every worker is a ~10ms
    fork instead of a fresh interpreter boot."""
    global _factory
    from ray_tpu._private.worker_factory import start_factory

    with _factory_lock:
        if _factory is not None and not _factory.alive():
            _factory = None
        if _factory is None:
            _factory = start_factory()
            import atexit

            atexit.register(_factory.stop)
        return _factory


def _validate_container(container: dict) -> str:
    """-> the container runtime binary; raises on a bad spec. Called
    BEFORE any listener/log-file resources exist so config errors
    (no podman on PATH, missing image) can't leak them."""
    import shutil

    runtime = container.get("runtime")
    if runtime is not None and shutil.which(runtime) is None:
        # An explicit runtime must exist too, or Popen would raise a
        # raw FileNotFoundError AFTER the listener/log resources exist.
        raise RuntimeError(
            f"runtime_env 'container' runtime {runtime!r} not on PATH")
    if runtime is None:
        runtime = next((r for r in ("podman", "docker")
                        if shutil.which(r)), None)
    if runtime is None:
        raise RuntimeError(
            "runtime_env 'container' needs podman or docker on PATH")
    if not container.get("image"):
        raise ValueError("runtime_env 'container' needs an 'image'")
    return runtime


def _container_argv(container: dict, addr: str, env: dict,
                    extra_env: dict | None = None) -> list[str]:
    """podman/docker argv for a containerized worker (reference:
    runtime_env/container.py builds `podman run` with the session dir
    and plasma socket mounted; here the connect-back socket dir and the
    framework checkout mount instead). Forwards the framework's own
    env keys PLUS every caller-supplied extra_env var (a container
    task's env_vars must be in the IN-IMAGE interpreter's env, not just
    the host-side Popen env)."""
    runtime = _validate_container(container)
    image = container["image"]
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sock_dir = os.path.dirname(addr)
    argv = [runtime, "run", "--rm", "--network=host",
            "-v", f"{sock_dir}:{sock_dir}",
            "-v", f"{pkg_root}:{pkg_root}:ro"]
    keys = ["RAY_TPU_WORKER_AUTHKEY", "PYTHONPATH",
            "RAY_TPU_DRIVER_CLIENT_ADDR", "RAY_TPU_NODE_TAG",
            "JAX_PLATFORMS", "RAY_TPU_SKIP_TPU_DETECTION"]
    keys += [k for k in (extra_env or {}) if k not in keys]
    for key in keys:
        # Bare `-e KEY`: podman/docker inherit the VALUE from the
        # Popen env, so the auth key and user secrets never appear on
        # the command line (/proc/<pid>/cmdline is world-readable).
        # `key in env` (not truthiness): an explicit empty string must
        # stay distinguishable from unset inside the image.
        if key in env:
            argv += ["-e", key]
    argv += list(container.get("run_options") or [])
    argv += [image, container.get("python", "python3"), "-m",
             "ray_tpu._private.worker_pool", addr]
    return argv


def _worker_env(base: dict, tpu_chips: "list[int] | None",
                extra_env: dict | None = None) -> dict:
    """Environment of a new worker process. ``tpu_chips=None`` is a CPU
    worker, pinned to the CPU so it can never take a chip from the
    process that owns it; a list is the chips leased to this worker
    (``accelerators.ChipLeases``): it sees those and no others, keeps
    whatever platform choice it inherits and is never pinned to the
    CPU by the runtime."""
    env = dict(base)
    if tpu_chips is None:
        env["RAY_TPU_SKIP_TPU_DETECTION"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
    else:
        from ray_tpu._private import accelerators, compile_cache

        env.update(accelerators.visible_chip_env(tpu_chips))
        compile_cache.child_env(env)
    if extra_env:
        env.update({k: str(v) for k, v in extra_env.items()})
    return env


def _spawn_worker(name: str, extra_env: dict | None = None,
                  tpu_chips: "list[int] | None" = None,
                  container: dict | None = None):
    """Start a worker as a fresh interpreter that connects back over a
    Unix socket (reference: worker_pool.h spawns language workers that
    connect to the raylet socket).

    Fast path: fork from the pre-imported factory template
    (worker_factory.py) — worker creation cost drops from an
    interpreter boot to a fork. Fallback (TPU workers, factory
    disabled via RAY_TPU_WORKER_FACTORY_DISABLE, or factory failure):
    subprocess + connect-back (rather than multiprocessing's spawn) so
    the child never re-imports the user's ``__main__`` — unguarded user
    scripts must keep working. Pool workers are CPU processes; only a
    worker that was leased chips (``tpu_chips``) may see a TPU.

    ``container``: a runtime_env container spec ({"image": ...,
    "run_options": [...]}) — the worker runs inside podman/docker with
    the connect-back socket dir and this checkout volume-mounted
    (reference: _private/runtime_env/container.py:26 wraps worker
    commands in `podman run`).
    """
    import secrets
    import subprocess
    import tempfile
    from multiprocessing.connection import Listener

    from ray_tpu._private.config import GLOBAL_CONFIG

    if container:
        _validate_container(container)  # raise before creating resources
    # Random suffix: concurrent spawns (e.g. several process actors
    # created back-to-back) must never race on one socket path.
    addr = os.path.join(
        tempfile.gettempdir(),
        f"ray_tpu_{os.getpid()}_{name}_{secrets.token_hex(4)}.sock")
    try:
        os.unlink(addr)
    except FileNotFoundError:
        pass
    authkey = secrets.token_bytes(16)
    listener = Listener(addr, family="AF_UNIX", authkey=authkey)
    env = _worker_env(os.environ, tpu_chips, extra_env)
    env["RAY_TPU_WORKER_AUTHKEY"] = authkey.hex()
    # The parent may have extended sys.path at runtime (e.g. a script
    # that inserted the framework's location); the child's `-m` import
    # must resolve ray_tpu before the hello handshake can deliver it.
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in sys.path if p] +
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    # Per-worker log files under the session dir (reference: worker
    # stdout/stderr files tailed by the log monitor); without a log dir
    # workers inherit the driver's console directly.
    log_dir = env.get("RAY_TPU_WORKER_LOG_DIR")
    log_path = None
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        log_path = os.path.join(log_dir, f"worker-{name}.log")
    proc = None
    if container:
        argv = _container_argv(container, addr, env,
                               extra_env=extra_env)
        log_file = open(log_path, "ab") if log_path else None
        proc = subprocess.Popen(argv, env=env,
                                stdout=log_file, stderr=log_file)
        if log_file is not None:
            log_file.close()
    if proc is None and tpu_chips is None \
            and not env.get("RAY_TPU_WORKER_FACTORY_DISABLE"):
        try:
            factory = _get_factory()
            # Workers whose env demands different jax/XLA import-time
            # config than the template booted with can't fork — the
            # already-imported jax would silently ignore it.
            if factory.compatible(env):
                # Fast path: a socketpair end rides SCM_RIGHTS through
                # the factory into the fork — the whole Listener/
                # accept/HMAC-challenge handshake disappears from the
                # spawn critical path.
                import socket as socket_mod
                from multiprocessing.connection import Connection

                parent_sock, child_sock = socket_mod.socketpair(
                    socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
                try:
                    proc = factory.spawn(
                        env=env, cwd=os.getcwd(), log_path=log_path,
                        pipe_fd=child_sock.fileno())
                finally:
                    child_sock.close()
                if proc is not None:
                    conn = Connection(parent_sock.detach())
                    listener.close()
                    try:
                        os.unlink(addr)
                    except FileNotFoundError:
                        pass
                    conn.send(("hello", list(sys.path)))
                    return proc, conn
        except Exception:  # noqa: BLE001 — Popen path still works
            import logging

            logging.getLogger("ray_tpu").warning(
                "worker factory unavailable; falling back to subprocess "
                "spawn", exc_info=True)
            proc = None
    if proc is None:
        log_file = open(log_path, "ab") if log_path else None
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.worker_pool", addr],
            env=env, cwd=os.getcwd(),
            stdout=log_file, stderr=log_file)
        if log_file is not None:
            log_file.close()  # the child holds the fd now
    try:
        # Listener.accept has no timeout arg; guard with a thread join.
        conn_box: list = []

        def accept():
            try:
                conn_box.append(listener.accept())
            except Exception as exc:  # noqa: BLE001
                conn_box.append(exc)

        t = threading.Thread(target=accept, daemon=True)
        t.start()
        t.join(timeout=float(GLOBAL_CONFIG.worker_startup_timeout_s))
        if not conn_box or isinstance(conn_box[0], Exception):
            proc.kill()
            raise WorkerCrashedError(
                f"worker {name} failed to connect: "
                f"{conn_box[0] if conn_box else 'timeout'}")
        conn = conn_box[0]
    finally:
        listener.close()
        try:
            os.unlink(addr)
        except FileNotFoundError:
            pass
    conn.send(("hello", list(sys.path)))
    return proc, conn


class PoolWorker:
    """One worker process + its pipe. One in-flight request at a time."""

    def __init__(self, index: int, extra_env: dict | None = None,
                 tpu_chips: "list[int] | None" = None,
                 container: dict | None = None):
        self.index = index
        self._lock = threading.Lock()
        # Function-blob digests this worker has already received (the
        # function-manager pattern: ship each function once per worker).
        self.known_digests: set[str] = set()
        self.proc, self.conn = _spawn_worker(
            f"w{index}", extra_env=extra_env, tpu_chips=tpu_chips,
            container=container)

    def request(self, msg: tuple) -> tuple:
        """Send one request and wait for its reply.

        Raises _WorkerUnavailable if the send itself fails (the request
        never reached the worker — safe to retry elsewhere), or
        WorkerCrashedError if the process dies after accepting it (the
        task may have started executing).
        """
        with self._lock:
            try:
                self.conn.send(msg)
            except (OSError, BrokenPipeError) as exc:
                raise _WorkerUnavailable(
                    f"worker {self.index} (pid {self.proc.pid}) "
                    f"unreachable: {exc!r}") from exc
            try:
                return self.conn.recv()
            except (EOFError, OSError) as exc:
                err = WorkerCrashedError(
                    f"worker {self.index} (pid "
                    f"{self.proc.pid}) died: {exc!r}")
                err.worker_pid = self.proc.pid  # OOM-kill attribution
                raise err from exc

    def send_nowait(self, msg: tuple) -> None:
        """Pipelined send: deliver one frame without waiting for its
        reply (the lease owner matches tagged replies itself). Raises
        _WorkerUnavailable when the frame never reached the worker."""
        with self._lock:
            try:
                self.conn.send(msg)
            except (OSError, BrokenPipeError, ValueError) as exc:
                raise _WorkerUnavailable(
                    f"worker {self.index} (pid {self.proc.pid}) "
                    f"unreachable: {exc!r}") from exc

    def recv_reply(self) -> tuple:
        """Pipelined receive (single reader: the lease owner). Raises
        WorkerCrashedError when the process died."""
        try:
            return self.conn.recv()
        except (EOFError, OSError) as exc:
            err = WorkerCrashedError(
                f"worker {self.index} (pid {self.proc.pid}) "
                f"died: {exc!r}")
            err.worker_pid = self.proc.pid  # OOM-kill attribution
            raise err from exc

    def alive(self) -> bool:
        return self.proc.poll() is None

    def stop(self) -> None:
        import subprocess

        try:
            with self._lock:
                self.conn.send(("exit",))
        except (OSError, BrokenPipeError):
            pass  # worker already dropped the pipe
        try:
            self.proc.wait(timeout=1.0)
        except subprocess.TimeoutExpired:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=1.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                # Gone before we return: what the worker held (a leased
                # chip) is handed on only after this.
                try:
                    self.proc.wait(timeout=10.0)
                except subprocess.TimeoutExpired:
                    pass  # unkillable (stuck in the kernel); move on
        self.conn.close()


class WorkerPool:
    """Fixed-size pool of task workers (reference: worker_pool.h pops a
    worker per lease, returns it after; prestart keeps latency low)."""

    def __init__(self, size: int, directory: ShmDirectory,
                 driver_client: ShmClient, max_size: int | None = None):
        self.size = size
        # Growth headroom for nested submission: an outer task blocked in
        # get() occupies its worker while the nested task needs another
        # (reference: the raylet starts workers on demand; CPU admission,
        # not pool size, bounds running tasks).
        self.max_size = max_size if max_size is not None else size * 4 + 8
        # How many workers to KEEP between tasks. A lazy pool (size=0,
        # no prestart) must still retain its grown workers — retiring
        # every worker at release makes every task pay a full process
        # spawn (observed: ~235ms/task vs ~1ms with a warm worker).
        self.idle_cap = size if size > 0 else min(4, self.max_size)
        self.directory = directory
        self.driver_client = driver_client
        self._lock = threading.Condition(threading.Lock())
        self._index_lock = threading.Lock()
        self._idle: list[PoolWorker] = []
        self._all_workers: set[PoolWorker] = set()
        self._next_index = 0
        self._num_leased = 0
        self._shutdown = False
        # Pipelined-batch counters (executor_stats drain stages).
        self._batch_lock = threading.Lock()
        self.batch_runs = 0     # multi-task lease runs
        self.batch_tasks = 0    # tasks entering run_task_batch
        self.batch_frames = 0   # pipelined frames actually sent
        self.batch_requeues = 0  # unstarted frames requeued (crashes)
        # Spawn in parallel: each worker blocks on interpreter boot +
        # socket handshake, so serial startup would be O(N).
        # size=0 is a legal lazy pool — no prestart, growth on demand
        # (many-node single-box clusters boot O(N) daemons; paying a
        # worker spawn per daemon up front is pure wasted wall-clock).
        from concurrent.futures import ThreadPoolExecutor

        if size <= 0:
            return
        with ThreadPoolExecutor(max_workers=min(size, 8)) as tpe:
            self._idle.extend(tpe.map(lambda _: self._new_worker(),
                                      range(size)))

    @staticmethod
    def _import_sensitive_env_vars(runtime_env: dict | None) -> dict:
        if not runtime_env:
            return {}
        from ray_tpu._private.worker_factory import (
            import_sensitive_subset,
        )

        return import_sensitive_subset(
            {str(k): str(v)
             for k, v in (runtime_env.get("env_vars") or {}).items()})

    def _new_worker(self, extra_env: dict | None = None,
                    container: dict | None = None) -> PoolWorker:
        with self._index_lock:
            index = self._next_index
            self._next_index += 1
        worker = PoolWorker(index, extra_env=extra_env,
                            container=container)
        with self._index_lock:
            self._all_workers.add(worker)
            self._all_workers = {w for w in self._all_workers
                                 if w.alive()}
        return worker

    def live_workers(self) -> list[PoolWorker]:
        """All live workers, idle or busy (memory-monitor view)."""
        with self._index_lock:
            return [w for w in self._all_workers if w.alive()]

    def _acquire(self) -> PoolWorker:
        grow = False
        with self._lock:
            while not self._idle and not self._shutdown:
                # Grow past `size` (up to max_size) instead of waiting:
                # every leased worker may be an outer task blocked on a
                # nested one that needs a worker of its own.
                if self._num_leased < self.max_size:
                    self._num_leased += 1
                    grow = True
                    break
                self._lock.wait(timeout=0.5)
            if self._shutdown:
                raise RuntimeError("worker pool is shut down")
            if not grow:
                worker = self._idle.pop()
                self._num_leased += 1
        if grow:
            try:
                return self._new_worker()
            except BaseException:
                # Give the lease slot back, or a failed spawn (e.g.
                # fork under memory pressure) pins the pool at max_size.
                with self._lock:
                    self._num_leased -= 1
                    self._lock.notify()
                raise
        if worker.alive():
            return worker
        # Died while idle (crash, memory-monitor kill): replace it
        # (spawn happens outside the condition lock — it is slow).
        worker.stop()
        try:
            return self._new_worker()
        except BaseException:
            with self._lock:
                self._num_leased -= 1
                self._lock.notify()
            raise

    def _release(self, worker: PoolWorker) -> None:
        # Spawn any replacement outside the pool lock (spawn is slow and
        # _new_worker must not nest under the condition lock).
        replacement = None
        if not worker.alive():
            if self._num_leased <= self.size:
                replacement = self._new_worker()
            else:
                worker.stop()  # shrink back toward the target size
        with self._lock:
            self._num_leased -= 1
            if self._shutdown:
                worker.stop()
                if replacement is not None:
                    replacement.stop()
                return
            if replacement is not None:
                self._idle.append(replacement)
            elif worker.alive():
                if len(self._idle) < self.idle_cap:
                    self._idle.append(worker)
                else:
                    # Surplus growth worker: retire it now that the
                    # burst is over (stop() can block; do it off-lock).
                    threading.Thread(target=worker.stop,
                                     daemon=True).start()
            self._lock.notify()

    # ----------------------------------------------------- pipelined batches

    def try_acquire_idle(self) -> "PoolWorker | None":
        """Non-blocking lease of an IDLE worker: never grows the pool,
        never waits (opportunistic extra lease runners for a batch)."""
        with self._lock:
            if self._shutdown:
                return None
            while self._idle:
                worker = self._idle.pop()
                if worker.alive():
                    self._num_leased += 1
                    return worker
                worker.stop()
        return None

    def run_task_batch(self, tasks: "list[_BatchTask]", on_result,
                       depth: int, tracker=None) -> None:
        """Execute a batch over pipelined multi-task worker leases.

        One blocking lease is taken up front; whenever a runner's
        pipeline is full (or deeper than the remaining queue) and tasks
        are still queued, IDLE workers are leased opportunistically —
        short tasks drain through one amortized lease, long tasks fan
        out across workers. Each lease keeps up to ``depth`` call-id-
        tagged frames in flight (the acquire/release and the function-
        digest check are paid once per run, not once per task).

        ``on_result(task, status, payload)`` fires exactly once per
        task from runner threads: status is "ok" (packed descriptors),
        "err" (exception blob) or "crash" (WorkerCrashedError — the
        task may have started). Worker death mid-pipeline fails ONLY
        the oldest in-flight frame; the rest were never started and are
        requeued onto a fresh lease.

        ``tracker`` (optional) observes lease composition for
        blocked-head parking: sent(key, token), done(key, token),
        drop_lease(key).
        """
        from collections import deque

        if not tasks:
            return
        state = _BatchState(deque(tasks), on_result, max(1, depth),
                            tracker, len(tasks))
        with self._batch_lock:
            self.batch_runs += 1
            self.batch_tasks += len(tasks)
        worker = self._acquire()
        self._batch_runner(worker, state)
        # The primary runner returned (queue empty, its frames done);
        # sibling runners may still hold in-flight frames.
        state.done.wait()

    def _maybe_extra_runner(self, state: "_BatchState") -> None:
        with state.lock:
            if not state.queue:
                return
        worker = self.try_acquire_idle()
        if worker is None:
            return
        threading.Thread(target=self._batch_runner,
                         args=(worker, state), daemon=True,
                         name="pool-batch-lease").start()

    def _batch_runner(self, worker: "PoolWorker",
                      state: "_BatchState") -> None:
        from collections import deque

        tracker = state.tracker
        while True:  # one iteration per lease (worker replaced on crash)
            lease_key = object()
            inflight: deque = deque()  # (call_id, task)
            next_id = 0
            crashed: BaseException | None = None
            while True:
                while len(inflight) < state.depth:
                    with state.lock:
                        task = (state.queue.popleft()
                                if state.queue else None)
                    if task is None:
                        break
                    blob = (None if task.digest in worker.known_digests
                            else task.func_blob)
                    next_id += 1
                    frame = ("task_seq", next_id, task.digest, blob,
                             task.args_blob, task.n_returns,
                             task.runtime_env, task.token,
                             task.client_addr,
                             task.sys_path if blob is not None
                             else None)
                    # Trace context, or the False perf-plane sentinel
                    # (this process's gate — workers follow the sender
                    # so a runtime disarm takes effect frame-by-frame).
                    slot = task.trace if task.trace is not None \
                        else (False if perf.PERF_ON else None)
                    if slot is not None or task.deadline is not None:
                        # Optional 11th/12th elements: trace/perf slot
                        # and the absolute deadline (absent on both ⇒
                        # the plain frame shape, byte-identical).
                        frame = frame + (slot,)
                    if task.deadline is not None:
                        frame = frame + (task.deadline,)
                    try:
                        worker.send_nowait(frame)
                    except _WorkerUnavailable as exc:
                        # Never delivered: this task is retryable as
                        # unstarted alongside the queued in-flight ones.
                        with state.lock:
                            state.queue.appendleft(task)
                        with self._batch_lock:
                            self.batch_requeues += 1
                        crashed = exc
                        break
                    worker.known_digests.add(task.digest)
                    inflight.append((next_id, task))
                    with self._batch_lock:
                        self.batch_frames += 1
                    if tracker is not None and task.token:
                        tracker.sent(lease_key, task.token)
                if crashed is not None:
                    break
                if not inflight:
                    self._release(worker)
                    return
                with state.lock:
                    more = bool(state.queue)
                if more:
                    self._maybe_extra_runner(state)
                try:
                    msg = worker.recv_reply()
                except WorkerCrashedError as exc:
                    crashed = exc
                    break
                if msg[0] != "task_done":
                    continue  # stray classic-protocol frame
                call_id, status, payload = msg[1], msg[2], msg[3]
                # Traced frames carry the worker's stage stamps as a
                # 5th element (frame pickup + exec start/end).
                wtrace = msg[4] if len(msg) > 4 else None
                task = None
                for i, (cid, t) in enumerate(inflight):
                    if cid == call_id:
                        task = t
                        del inflight[i]
                        break
                if task is None:
                    continue
                if tracker is not None and task.token:
                    tracker.done(lease_key, task.token)
                self._complete_one(state, task, status, payload, wtrace)
            # Worker died (or refused the frame). The OLDEST in-flight
            # frame was executing — it may have side effects, so it
            # fails; everything behind it never started and is retried
            # on a fresh lease.
            if tracker is not None:
                tracker.drop_lease(lease_key)
            started = inflight.popleft() if inflight else None
            if started is not None:
                self._complete_one(state, started[1], "crash", crashed)
            if inflight:
                with self._batch_lock:
                    self.batch_requeues += len(inflight)
            with state.lock:
                state.queue.extendleft(t for _, t in reversed(inflight))
                remaining = bool(state.queue)
            self._release(worker)
            if not remaining:
                return
            try:
                worker = self._acquire()
            except BaseException:  # noqa: BLE001 — pool shut down
                with state.lock:
                    stranded = list(state.queue)
                    state.queue.clear()
                for task in stranded:
                    self._complete_one(state, task, "crash", crashed)
                return

    def _complete_one(self, state: "_BatchState", task: "_BatchTask",
                      status: str, payload, wtrace=None) -> None:
        try:
            state.on_result(task, status, payload, wtrace)
        finally:
            with state.lock:
                state.remaining -= 1
                if state.remaining <= 0:
                    state.done.set()

    # ------------------------------------------------------------- task path

    def marshal_args(self, args: tuple, kwargs: dict,
                     promote: Callable[[Any], ShmDescriptor]) -> bytes:
        """Replace top-level ObjectRef args with _ShmRef descriptors
        (promoting driver-held values into shm) and frame the rest."""
        from ray_tpu._private.object_ref import ObjectRef

        if not any(isinstance(a, ObjectRef) for a in args) \
                and not any(isinstance(v, ObjectRef)
                            for v in kwargs.values()):
            # Ref-free small-immutable calls skip the pickle round trip
            # (the worker's deserialize dispatches on the raw sentinel).
            raw = serialization.try_serialize_raw((args, kwargs))
            if raw is not None:
                return raw

        def convert(a):
            if isinstance(a, ObjectRef):
                return _ShmRef(promote(a))
            return a

        conv_args = tuple(convert(a) for a in args)
        conv_kwargs = {k: convert(v) for k, v in kwargs.items()}
        return serialization.serialize_framed((conv_args, conv_kwargs))

    def run_task_blobs(self, digest: str, func_blob: bytes, args_blob: bytes,
                       n_returns: int, return_ids: list[ObjectID],
                       runtime_env: dict | None = None,
                       task_token: str | None = None,
                       client_addr: str | None = None,
                       sys_path: list | None = None,
                       trace: tuple | None = None,
                       stages_out: dict | None = None,
                       ) -> list[tuple[ObjectID, Any]]:
        """Execute on a pool worker; returns [(return_id, value)] pairs.

        ``trace`` arms worker-side stage stamping for this task;
        ``stages_out`` (a dict) receives the worker's frame/exec
        timestamps from the reply.

        The function blob only crosses the pipe the first time a given
        worker sees its digest (function-manager pattern); afterwards
        the worker's cache is addressed by digest alone.

        Raises WorkerCrashedError (system failure) or _RemoteTaskError
        (application failure, carrying the remote traceback). A worker
        that proves unreachable before accepting the request is replaced
        and the request retried on another — no work was started, so
        this is invisible to the caller.
        """
        sensitive = self._import_sensitive_env_vars(runtime_env)
        container = (runtime_env or {}).get("container")
        if sensitive or container:
            # jax/XLA read these at IMPORT time; a shared worker (and
            # any fork of the pre-imported factory template) has jax
            # frozen already, so per-task os.environ application would
            # be silently ignored. Such tasks — and container tasks,
            # whose interpreter must boot INSIDE the image — get a
            # dedicated fresh worker whose spawn env carries the vars,
            # under the SAME lease accounting as the shared pool, so N
            # in-flight env-sensitive tasks still respect max_size (and
            # a shut-down pool refuses them).
            with self._lock:
                while self._num_leased >= self.max_size \
                        and not self._shutdown:
                    self._lock.wait(timeout=0.5)
                if self._shutdown:
                    raise RuntimeError("worker pool is shut down")
                self._num_leased += 1
            worker = None
            try:
                worker = self._new_worker(
                    extra_env=dict(runtime_env.get("env_vars") or {}),
                    container=container)
                msg = ("task", digest, func_blob, args_blob, n_returns,
                       runtime_env, task_token, client_addr, sys_path)
                slot = trace if trace is not None \
                    else (False if perf.PERF_ON else None)
                if slot is not None:
                    msg = msg + (slot,)
                reply = worker.request(msg)
                self._copy_reply_stages(reply, stages_out)
                return self._unpack_reply(reply, return_ids)
            finally:
                if worker is not None:
                    worker.stop()
                    with self._index_lock:
                        self._all_workers.discard(worker)
                with self._lock:
                    self._num_leased -= 1
                    self._lock.notify()
        while True:
            worker = self._acquire()
            send_blob = None if digest in worker.known_digests else func_blob
            msg = ("task", digest, send_blob, args_blob, n_returns,
                   runtime_env, task_token, client_addr,
                   sys_path if send_blob is not None else None)
            slot = trace if trace is not None \
                else (False if perf.PERF_ON else None)
            if slot is not None:
                msg = msg + (slot,)
            try:
                reply = worker.request(msg)
            except _WorkerUnavailable:
                continue  # _release (in finally) already spawns a live one
            finally:
                self._release(worker)
            worker.known_digests.add(digest)
            self._copy_reply_stages(reply, stages_out)
            return self._unpack_reply(reply, return_ids)

    @staticmethod
    def _copy_reply_stages(reply: tuple, stages_out: dict | None) -> None:
        if stages_out is not None and len(reply) > 2 and reply[2]:
            stages_out.update(reply[2])

    def _unpack_reply(self, reply: tuple,
                      return_ids: list[ObjectID]) -> list[tuple[ObjectID, Any]]:
        if reply[0] == "err":
            exc, tb = serialization.deserialize_from_buffer(
                memoryview(reply[1]))
            raise _RemoteTaskError(exc, tb)
        results = []
        for rid, packed in zip(return_ids, reply[1]):
            if packed[0] == "inline":
                value = serialization.deserialize_from_buffer(
                    memoryview(packed[1]))
            elif packed[0] == "arena":
                desc = ArenaDescriptor(packed[1], packed[2])
                self.directory.register_arena(rid, desc)
                value = self.driver_client.get(desc)
            elif packed[0] == "shm":
                desc = ShmDescriptor(packed[1], packed[2])
                self.directory.adopt(rid, desc)
                value = self.driver_client.get(desc)
            else:  # ("err", blob) — this return value failed to pickle
                exc, tb = serialization.deserialize_from_buffer(
                    memoryview(packed[1]))
                raise _RemoteTaskError(exc, tb)
            results.append((rid, value))
        return results

    def shutdown(self) -> None:
        with self._lock:
            self._shutdown = True
            workers = list(self._idle)
            self._idle.clear()
            self._lock.notify_all()
        for w in workers:
            w.stop()


class _BatchState:
    """Shared state of one run_task_batch call: the task queue lease
    runners pull from, completion accounting, and the parking
    tracker."""

    __slots__ = ("queue", "on_result", "depth", "tracker", "remaining",
                 "lock", "done")

    def __init__(self, queue, on_result, depth, tracker, n):
        self.queue = queue
        self.on_result = on_result
        self.depth = depth
        self.tracker = tracker
        self.remaining = n
        self.lock = threading.Lock()
        self.done = threading.Event()


class _RemoteTaskError(Exception):
    """Carries a worker-side exception + its remote traceback string."""

    def __init__(self, cause: BaseException, remote_tb: str):
        super().__init__(str(cause))
        self.cause = cause
        self.remote_tb = remote_tb


class _WorkerUnavailable(Exception):
    """The request could not be delivered (worker already dead)."""


# --------------------------------------------------------------------------
# Process actors
# --------------------------------------------------------------------------


class ProcessActor:
    """An actor bound to a dedicated worker process.

    Mirrors LocalActor's interface (submit/kill/is_dead) so the Runtime
    treats both uniformly; calls execute in submission order in the
    worker process (reference: a Ray actor IS a worker process with an
    ordered scheduling queue, transport/actor_scheduling_queue.h).
    """

    def __init__(self, actor_id: ActorID, cls: type, init_args: tuple,
                 init_kwargs: dict, runtime, *, max_restarts: int = 0,
                 max_pending_calls: int = -1,
                 max_concurrency: int = 1,
                 creation_return_id: ObjectID | None = None,
                 on_death: Callable[[ActorID, str], None] | None = None,
                 on_restart: Callable[[ActorID], None] | None = None,
                 runtime_env: dict | None = None,
                 tpu_chips: "list[int] | None" = None):
        import queue as queue_mod

        self.actor_id = actor_id
        self._cls = cls
        # Chips leased to this actor's process (kept across restarts).
        self._tpu_chips = tpu_chips
        self._max_concurrency = max(1, int(max_concurrency))
        self._runtime_env = runtime_env
        self._init_args = init_args
        self._init_kwargs = init_kwargs
        self._runtime = runtime
        self._max_restarts = max_restarts
        self._max_pending_calls = max_pending_calls
        self._on_death = on_death
        self._on_restart = on_restart
        self._num_restarts = 0
        self._queue: queue_mod.Queue = queue_mod.Queue()
        self._pending = 0
        self._lock = threading.Lock()
        self._dead = False
        self._death_reason: str | None = None
        self._creation_return_id = creation_return_id
        self._worker: PoolWorker | None = None
        self._started = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"ray_tpu-pactor-{cls.__name__}")
        self._thread.start()

    # Interface shared with LocalActor ------------------------------------

    def submit(self, call) -> None:
        from ray_tpu.exceptions import PendingCallsLimitExceeded

        with self._lock:
            if self._dead:
                self._fail_call(call, ActorDiedError(
                    self.actor_id, self._death_reason or "actor has died"))
                return
            if 0 <= self._max_pending_calls <= self._pending:
                self._fail_call(call, PendingCallsLimitExceeded(
                    f"actor {self._cls.__name__} has {self._pending} "
                    f"pending calls"))
                return
            self._pending += 1
            self._queue.put(call)

    def kill(self, reason: str = "killed via kill()",
             no_restart: bool = True) -> None:
        restartable = (not no_restart) and self._num_restarts < self._max_restarts
        # Terminate the process FIRST: an in-flight request holds the
        # PoolWorker lock until its recv fails, and _mark_dead's
        # worker.stop() needs that lock — killing after would deadlock.
        worker = self._worker
        if worker is not None and worker.alive():
            worker.proc.terminate()
        self._mark_dead(reason, notify=not restartable)
        self._queue.put(None)
        if restartable:
            self._restart()

    def is_dead(self) -> bool:
        with self._lock:
            return self._dead

    def wait_started(self, timeout: float | None = None) -> bool:
        return self._started.wait(timeout)

    # Internals ------------------------------------------------------------

    def _fail_call(self, call, error: BaseException) -> None:
        for rid in call.return_ids:
            self._runtime.store.put_error(rid, error)

    def _marshal(self, args: tuple, kwargs: dict) -> bytes:
        return serialization.serialize_framed((args, kwargs))

    def _run(self) -> None:
        try:
            self._worker = PoolWorker(-1, tpu_chips=self._tpu_chips)
            record = getattr(self, "_gcs_record", None)
            if record is not None:
                # Actor-table placement: the dedicated process's pid
                # (also corrects the stale pid after a restart respawn).
                record.pid = self._worker.proc.pid
            cls_blob = serialization.dumps_function(self._cls)
            args_blob = self._marshal(self._init_args, self._init_kwargs)
            reply = self._worker.request(
                ("actor_new", cls_blob, args_blob, self._runtime_env,
                 self._max_concurrency))
            if reply[0] == "err":
                exc, tb = serialization.deserialize_from_buffer(
                    memoryview(reply[1]))
                raise ActorError(exc, tb, f"{self._cls.__name__}.__init__")
        except BaseException as exc:  # noqa: BLE001
            self._mark_dead(f"constructor failed: {exc!r}")
            if self._creation_return_id is not None:
                self._runtime.store.put_error(self._creation_return_id, exc)
            return
        if self._creation_return_id is not None:
            self._runtime.store.put(self._creation_return_id, None)
        self._started.set()
        if self._max_concurrency > 1:
            self._run_concurrent()
            return
        while True:
            call = self._queue.get()
            if call is None:
                return
            try:
                with self._lock:
                    self._pending -= 1
                    if self._dead:
                        self._fail_call(call, ActorDiedError(
                            self.actor_id,
                            self._death_reason or "actor died"))
                        continue
                from ray_tpu._private.actor_runtime import (
                    _call_deadline_error,
                )

                expired = _call_deadline_error(call, self._cls.__name__)
                if expired is not None:
                    self._fail_call(call, expired)
                    continue
                try:
                    args_blob = self._marshal(call.args, call.kwargs)
                except Exception as exc:  # noqa: BLE001 — unpicklable args
                    self._fail_call(call, ActorError(
                        exc, "", f"{self._cls.__name__}.{call.method_name} "
                        f"(argument serialization)"))
                    continue
                try:
                    reply = self._worker.request(
                        ("actor_call", call.method_name, args_blob,
                         len(call.return_ids)))
                    if reply[0] == "err":
                        exc, tb = serialization.deserialize_from_buffer(
                            memoryview(reply[1]))
                        self._fail_call(call, ActorError(
                            exc, tb,
                            f"{self._cls.__name__}.{call.method_name}"))
                        continue
                    self._store_call_results(call, reply[1])
                except (WorkerCrashedError, _WorkerUnavailable):
                    self._handle_crash(call)
                    return
                except BaseException as exc:  # noqa: BLE001 — never kill
                    # the executor thread silently: fail the call and
                    # keep serving.
                    self._fail_call(call, exc)
            finally:
                # Unbind before re-blocking in get(): a stale frame
                # local would keep the last call's args (and nested
                # ObjectRefs) alive until the next call arrives.
                call = None

    def _store_call_results(self, call, packed_list) -> None:
        for rid, packed in zip(call.return_ids, packed_list):
            if packed[0] == "inline":
                value = serialization.deserialize_from_buffer(
                    memoryview(packed[1]))
            elif packed[0] == "arena":
                desc = ArenaDescriptor(packed[1], packed[2])
                self._runtime.shm_directory.register_arena(rid, desc)
                value = self._runtime.shm_client.get(desc)
            elif packed[0] == "shm":
                desc = ShmDescriptor(packed[1], packed[2])
                self._runtime.shm_directory.adopt(rid, desc)
                value = self._runtime.shm_client.get(desc)
            else:  # ("err", blob): this return value failed to pickle
                exc, tb = serialization.deserialize_from_buffer(
                    memoryview(packed[1]))
                self._fail_call(call, ActorError(
                    exc, tb, f"{self._cls.__name__}.{call.method_name}"))
                return
            self._runtime.store.put(rid, value)

    def _run_concurrent(self) -> None:
        """Multiplexed mode (max_concurrency > 1): submissions stream to
        the worker tagged with call ids, a reader thread matches
        interleaved replies, and up to max_concurrency calls execute
        simultaneously worker-side. Per-caller ordering is NOT
        guaranteed — the same trade the reference makes for
        max_concurrency > 1 actors."""
        worker = self._worker
        # Generation guard: _restart bumps _num_restarts BEFORE spawning
        # the replacement thread, so comparing it is race-free (checking
        # self._worker is not — it's replaced only after the slow
        # process spawn completes, leaving a window where a stale sender
        # could steal a post-restart call).
        my_gen = self._num_restarts
        conn = worker.conn
        send_lock = threading.Lock()
        pending: dict[int, Any] = {}
        pending_lock = threading.Lock()
        next_id = [0]

        def reader():
            while True:
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    break
                if msg[0] != "reply":
                    continue
                _, call_id, status, payload = msg
                with pending_lock:
                    call = pending.pop(call_id, None)
                if call is None:
                    continue
                with self._lock:
                    # _pending counts queued + in-flight here, so
                    # max_pending_calls bounds the true outstanding work
                    # (decrement only once the reply landed).
                    self._pending = max(0, self._pending - 1)
                # The reader must never die silently: one bad reply
                # (shm attach failure, undeserializable payload) fails
                # ITS call and the loop keeps serving — otherwise every
                # in-flight call hangs forever with the pipe still open.
                try:
                    if status == "err":
                        exc, tb = serialization.deserialize_from_buffer(
                            memoryview(payload))
                        self._fail_call(call, ActorError(
                            exc, tb,
                            f"{self._cls.__name__}.{call.method_name}"))
                    else:
                        self._store_call_results(call, payload)
                except BaseException as exc:  # noqa: BLE001
                    self._fail_call(call, exc)
            # Pipe closed: fail everything still in flight. The reader
            # is the single authority for crash handling in concurrent
            # mode (the sender defers to it); skip if this worker
            # generation was already replaced or cleanly killed.
            with pending_lock:
                stranded = list(pending.values())
                pending.clear()
            for call in stranded:
                self._fail_call(call, ActorDiedError(
                    self.actor_id, "actor process died with calls "
                    "in flight"))
            if self._num_restarts == my_gen and not self.is_dead():
                restartable = self._num_restarts < self._max_restarts
                self._mark_dead("actor process died",
                                notify=not restartable)
                if restartable:
                    self._restart()

        reader_thread = threading.Thread(
            target=reader, daemon=True,
            name=f"ray_tpu-pactor-read-{self._cls.__name__}")
        reader_thread.start()

        while True:
            call = self._queue.get()
            if call is None:
                return
            if self._num_restarts != my_gen:
                # A crash-restart replaced this generation while we were
                # blocked on the queue: hand the call to the NEW
                # sender and exit (stale senders must not steal work).
                self._queue.put(call)
                return
            with self._lock:
                # NOTE: _pending is NOT decremented here — it keeps
                # counting until the reply arrives (reader thread), so
                # max_pending_calls bounds queued + in-flight.
                if self._dead:
                    self._pending = max(0, self._pending - 1)
                    self._fail_call(call, ActorDiedError(
                        self.actor_id, self._death_reason or "actor died"))
                    continue
            from ray_tpu._private.actor_runtime import (
                _call_deadline_error,
            )

            expired = _call_deadline_error(call, self._cls.__name__)
            if expired is not None:
                with self._lock:
                    self._pending = max(0, self._pending - 1)
                self._fail_call(call, expired)
                continue
            try:
                args_blob = self._marshal(call.args, call.kwargs)
            except Exception as exc:  # noqa: BLE001 — unpicklable args
                with self._lock:
                    self._pending = max(0, self._pending - 1)
                self._fail_call(call, ActorError(
                    exc, "", f"{self._cls.__name__}.{call.method_name} "
                    f"(argument serialization)"))
                continue
            call_id = next_id[0]
            next_id[0] += 1
            with pending_lock:
                pending[call_id] = call
            try:
                with send_lock:
                    conn.send(("actor_call_async", call_id,
                               call.method_name, args_blob,
                               len(call.return_ids)))
            except (OSError, BrokenPipeError):
                with pending_lock:
                    pending.pop(call_id, None)
                with self._lock:
                    self._pending = max(0, self._pending - 1)
                # Fail this call; death/restart is the READER's job
                # (single authority — two restart paths would race).
                self._fail_call(call, ActorDiedError(
                    self.actor_id,
                    f"actor process died sending {call.method_name}()"))
                return
            # Unbind before re-blocking (pending holds the call until
            # the reader delivers its reply; the stale frame local
            # would extend that past delivery).
            call = None
            args_blob = None

    def _handle_crash(self, call) -> None:
        reason = f"actor process died executing {call.method_name}()"
        restartable = self._num_restarts < self._max_restarts
        self._fail_call(call, ActorDiedError(self.actor_id, reason))
        self._mark_dead(reason, notify=not restartable)
        if restartable:
            self._restart()

    def _mark_dead(self, reason: str, notify: bool = True) -> None:
        import queue as queue_mod

        with self._lock:
            if self._dead:
                return
            self._dead = True
            self._death_reason = reason
            drained = []
            try:
                while True:
                    item = self._queue.get_nowait()
                    if item is not None:
                        drained.append(item)
            except queue_mod.Empty:
                pass
            self._pending = 0
        for call in drained:
            self._fail_call(call, ActorDiedError(self.actor_id, reason))
        worker = self._worker
        if worker is not None:
            worker.stop()
        if notify and self._on_death is not None:
            self._on_death(self.actor_id, reason)

    def _restart(self) -> None:
        with self._lock:
            self._num_restarts += 1
            self._dead = False
            self._death_reason = None
        self._started.clear()
        self._creation_return_id = None
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"ray_tpu-pactor-{self._cls.__name__}-r{self._num_restarts}")
        self._thread.start()
        if self._on_restart is not None:
            self._on_restart(self.actor_id)


# --------------------------------------------------------------------------
# Worker executable entry: python -m ray_tpu._private.worker_pool <socket>
# --------------------------------------------------------------------------

if __name__ == "__main__":
    from multiprocessing.connection import Client

    # Serve from the canonically-imported module, not this __main__
    # alias: unpickled _ShmRef instances come from the import-path copy
    # and must be the same class the serving loop isinstance-checks.
    from ray_tpu._private.worker_pool import worker_main as _worker_main

    _addr = sys.argv[1]
    _authkey = bytes.fromhex(os.environ.pop("RAY_TPU_WORKER_AUTHKEY"))
    _conn = Client(_addr, family="AF_UNIX", authkey=_authkey)
    _worker_main(_conn)
