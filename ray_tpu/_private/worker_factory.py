"""Fork-server worker factory: millisecond worker/actor process spawn.

Reference: Ray keeps worker startup off the task critical path with
prestarted language workers (src/ray/raylet/worker_pool.h "Starts a
number of workers ahead of time"). This is the TPU-native single-box
analogue taken further: instead of paying a full interpreter boot +
framework import (~1-2s of CPU) per worker, each daemon runs ONE
pre-imported template process; every subsequent worker is an os.fork()
of it (~10ms, memory shared copy-on-write). On the 1-to-few-core hosts
that drive TPU slices this is the difference between actor creation at
~1/s and ~50/s.

Topology:
  daemon/driver process
    └─ factory (python -m ray_tpu._private.worker_factory <sock> <ppid>)
         ├─ forked worker 1  ── connects back to the pool's Listener
         ├─ forked worker 2     and runs worker_pool.worker_main, byte-
         └─ ...                 identical to a Popen'd worker from there

The factory is single-threaded (fork-safe by construction), reaps its
children, and exits when its parent dies (ppid watch). Spawn requests
ride one-shot connections on a 0700-dir unix socket. Workers needing a
leased TPU chips or a different interpreter never use the factory —
callers fall back to the subprocess path.
"""

from __future__ import annotations

import os
import pickle
import socket
import struct
import sys
import time

_LEN = struct.Struct(">I")


def _send_msg(sock: socket.socket, obj) -> None:
    blob = pickle.dumps(obj)
    sock.sendall(_LEN.pack(len(blob)) + blob)


def _recv_msg(sock: socket.socket):
    buf = b""
    while len(buf) < _LEN.size:
        chunk = sock.recv(_LEN.size - len(buf))
        if not chunk:
            raise EOFError("factory peer closed")
        buf += chunk
    (length,) = _LEN.unpack(buf)
    parts = []
    while length > 0:
        chunk = sock.recv(min(length, 1 << 20))
        if not chunk:
            raise EOFError("factory peer closed")
        parts.append(chunk)
        length -= len(chunk)
    return pickle.loads(b"".join(parts))


# --------------------------------------------------------------------------
# Factory process
# --------------------------------------------------------------------------


def _child_exec(req: dict, pipe_fd: int | None = None) -> None:
    """Post-fork setup then the normal worker serve loop. Never returns."""
    rc = 1
    try:
        import gc
        import signal

        # The template disabled gc around its freeze(); workers do real
        # work and must collect cycles again (frozen template objects
        # stay permanent — the child never pages them in via gc).
        gc.enable()
        signal.signal(signal.SIGCHLD, signal.SIG_DFL)
        env = req.get("env") or {}
        # REPLACE the environment (Popen semantics), don't merge: a var
        # the driver deleted after factory start must not leak into the
        # worker.
        os.environ.clear()
        os.environ.update({k: str(v) for k, v in env.items()})
        # Back-channel to the template (argv survives the fork): a
        # worker that ends up importing jax touches this marker so the
        # template upgrades itself for future forks (two-stage boot).
        if len(sys.argv) > 1:
            os.environ["RAY_TPU_FACTORY_MARKER"] = os.path.join(
                os.path.dirname(sys.argv[1]), JAX_MARKER)
        # The Popen path hands PYTHONPATH to a fresh interpreter; a fork
        # must apply it by hand (and pip/conda runtime envs layer their
        # site-packages the same way at task level).
        for p in reversed(env.get("PYTHONPATH", "").split(os.pathsep)):
            if p and p not in sys.path:
                sys.path.insert(0, p)
        if req.get("cwd"):
            try:
                os.chdir(req["cwd"])
            except OSError:
                pass  # missing cwd: worker runs where it can
        log_path = req.get("log_path")
        if log_path:
            fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                         0o644)
        else:
            fd = os.open(os.devnull, os.O_WRONLY)
        os.dup2(fd, 1)
        os.dup2(fd, 2)
        os.close(fd)
        from ray_tpu._private.worker_pool import worker_main

        os.environ.pop("RAY_TPU_WORKER_AUTHKEY", None)
        if pipe_fd is not None:
            # Kernel-passed socketpair end (SCM_RIGHTS through the
            # factory): possession IS the authentication — no listener
            # accept, no HMAC challenge round-trips.
            from multiprocessing.connection import Connection

            conn = Connection(pipe_fd)
        else:
            from multiprocessing.connection import Client

            authkey = bytes.fromhex(req["authkey"])
            conn = Client(req["addr"], family="AF_UNIX",
                          authkey=authkey)
        worker_main(conn)
        rc = 0
    except BaseException:  # noqa: BLE001 — log to the worker's own log
        import traceback

        traceback.print_exc()
    finally:
        os._exit(rc)


def _freeze_heap() -> None:
    # Freeze the template heap: everything imported so far moves to the
    # permanent generation, so the CHILDREN's garbage collector never
    # scans (and copy-on-write-faults) those pages. Without this every
    # fork pays ~tens of ms of CoW churn the moment its first gc cycle
    # walks the inherited jax/numpy object graph — at actor-creation
    # waves that churn IS the bottleneck on 1-core hosts.
    import gc

    gc.disable()
    gc.collect()
    gc.freeze()


JAX_MARKER = "jax_wanted"


def factory_main(sock_path: str, parent_pid: int) -> None:
    # Pre-import the worker stack ONCE; every fork shares these pages.
    # Workers are CPU processes (the daemon owns the TPU), so importing
    # jax here is safe and saves each fork its heaviest import. But the
    # jax import is ~3x the rest of the template boot, and a fleet of
    # daemons booting factories serializes those imports on the host's
    # cores right when an actor/task wave needs them — so the default
    # is a TWO-STAGE boot: come up with only the worker stack + numpy
    # (fast READY, cheap-but-jaxless forks) and import jax later, the
    # first time a forked worker actually pulls jax in. The children
    # can't message us over the spawn socket (they hold no client), so
    # the signal is a marker file in the socket's private 0700 dir:
    # forks inherit this argv, notice 'jax' landing in their
    # sys.modules, and touch it; we poll it from the accept loop and
    # upgrade between spawn requests.
    #   RAY_TPU_FACTORY_JAX=eager restores the old import-at-boot
    #   behaviour; RAY_TPU_FACTORY_LEAN=1 (or FACTORY_JAX=off) never
    #   imports jax into the template at all.
    import ray_tpu._private.worker_pool  # noqa: F401

    mode = os.environ.get("RAY_TPU_FACTORY_JAX", "auto").lower()
    if os.environ.get("RAY_TPU_FACTORY_LEAN",
                      "0").lower() not in ("", "0", "false", "no"):
        mode = "off"
    jax_loaded = False
    if mode == "eager":
        try:
            import jax  # noqa: F401

            jax_loaded = True
        except Exception:  # noqa: BLE001 — workers will import lazily
            pass
    elif mode != "off":
        try:
            # Everything a non-jax worker touches on its first task,
            # so stage-one forks are as cheap as fully-warmed ones:
            # numpy (result packing, user arrays) plus the worker-side
            # runtime modules and their stdlib closure (measured as the
            # sys.modules delta of a fresh fork's first no-op task).
            import multiprocessing.connection  # noqa: F401
            import pathlib  # noqa: F401
            import shutil  # noqa: F401
            import tempfile  # noqa: F401
            import zipfile  # noqa: F401

            import numpy  # noqa: F401

            import ray_tpu._private.rpc  # noqa: F401
            import ray_tpu._private.runtime_env_packaging  # noqa: F401
            import ray_tpu._private.worker_client  # noqa: F401
        except Exception:  # noqa: BLE001
            pass
    _freeze_heap()
    marker_path = os.path.join(os.path.dirname(sock_path), JAX_MARKER)

    def _maybe_upgrade() -> None:
        nonlocal jax_loaded
        if jax_loaded or mode in ("off", "eager"):
            return
        if not os.path.exists(marker_path):
            return
        try:
            import jax  # noqa: F401
        except Exception:  # noqa: BLE001 — keep serving lean forks
            pass
        jax_loaded = True  # don't re-attempt either way
        _freeze_heap()

    server = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    server.bind(sock_path)
    server.listen(64)
    server.settimeout(1.0)
    # Readiness handshake: the parent waits for this byte so the first
    # spawn request never races the bind.
    print("FACTORY_READY", flush=True)
    while True:
        # Reap finished workers (they are OUR children post-fork).
        try:
            while os.waitpid(-1, os.WNOHANG)[0] != 0:
                pass
        except ChildProcessError:
            pass
        if os.getppid() != parent_pid:
            break  # daemon died; orphaned factory must not linger
        try:
            conn, _ = server.accept()
        except socket.timeout:
            # Idle moment: safe to pay the ~0.5s jax import without
            # stalling a queued spawn request.
            _maybe_upgrade()
            continue
        except OSError:
            break
        pipe_fd: int | None = None
        try:
            # Every request starts with a 2-byte marker; b"FD" carries
            # the worker's pre-connected pipe end as ancillary data.
            marker, fds, _, _ = socket.recv_fds(conn, 2, 1)
            while len(marker) < 2:
                more = conn.recv(2 - len(marker))
                if not more:
                    raise EOFError("factory peer closed")
                marker += more
            if marker == b"FD" and fds:
                pipe_fd = fds[0]
            req = _recv_msg(conn)
            if req.get("op") == "exit":
                _send_msg(conn, {"ok": True})
                break
            pid = os.fork()
            if pid == 0:
                server.close()
                conn.close()
                _child_exec(req, pipe_fd)  # never returns
            _send_msg(conn, {"ok": True, "pid": pid})
        except BaseException as exc:  # noqa: BLE001 — keep serving
            try:
                _send_msg(conn, {"ok": False, "error": repr(exc)})
            except OSError:
                pass  # requester hung up before the reply
        finally:
            if pipe_fd is not None:
                try:
                    os.close(pipe_fd)  # the child inherited its copy
                except OSError:
                    pass  # child inherited the fd; ours may be gone
            try:
                conn.close()
            except OSError:
                pass  # requester already hung up
    try:
        server.close()
        os.unlink(sock_path)
    except OSError:
        pass  # socket path already removed


# --------------------------------------------------------------------------
# Client side (runs in the daemon/driver process)
# --------------------------------------------------------------------------


class PidHandle:
    """Popen-compatible handle for a process that is NOT our child (the
    factory's child). Liveness and signalling go through a pidfd
    (os.pidfd_open), which is immune to PID recycling: after the
    factory reaps the worker and the kernel reuses the PID, signal-0
    liveness would report an unrelated process as 'our worker' and
    terminate()/kill() could hit an innocent bystander. The pidfd
    stays bound to the original process forever (readable once it
    exits) regardless of reaping or reuse."""

    def __init__(self, pid: int):
        self.pid = pid
        self._rc: int | None = None
        self._pidfd: int | None = -1  # sentinel: kill(pid, 0) fallback
        try:
            self._pidfd = os.pidfd_open(pid)
        except ProcessLookupError:
            # Genuinely gone (exited and reaped before we got here).
            self._pidfd = None
            self._rc = -1
        except (OSError, AttributeError):
            # No pidfd support (kernel < 5.3, seccomp EPERM/ENOSYS, or
            # no os.pidfd_open at all): the worker is LIVE — fall back
            # to signal-0 liveness, imperfect but never dead-on-arrival.
            self._pidfd = -1

    def __del__(self):
        if self._pidfd is not None and self._pidfd >= 0:
            try:
                os.close(self._pidfd)
            except OSError:
                pass  # pidfd already closed at GC

    def poll(self) -> int | None:
        if self._rc is not None:
            return self._rc
        if self._pidfd == -1:  # no-pidfd fallback
            try:
                os.kill(self.pid, 0)
                return None
            except ProcessLookupError:
                self._rc = -1
                return self._rc
            except PermissionError:
                return None
        import select

        readable, _, _ = select.select([self._pidfd], [], [], 0)
        if readable:
            self._rc = self._exit_status()
        return self._rc

    def _exit_status(self) -> int:
        """Recover the worker's REAL exit status where the kernel
        allows it: waitid(P_PIDFD, WEXITED|WNOWAIT) reads the status
        without consuming it (the factory is the reaping parent, and
        on same-process children a later wait must still succeed).
        Falls back to -1 — 'exited, status unknown' — when the kernel
        predates P_PIDFD or the process was already reaped by the
        factory (waitid is parent-only)."""
        try:
            p_pidfd = os.P_PIDFD  # Python 3.9+/Linux 5.4+
        except AttributeError:
            return -1
        try:
            res = os.waitid(p_pidfd, self._pidfd,
                            os.WEXITED | os.WNOWAIT | os.WNOHANG)
        except (ChildProcessError, OSError):
            return -1  # not our child / already reaped
        if res is None:
            return -1  # raced: readable but not yet waitable
        if res.si_code == os.CLD_EXITED:
            return res.si_status
        # Killed by signal: report the negated signal number, matching
        # subprocess.Popen.returncode semantics.
        return -res.si_status

    def wait(self, timeout: float | None = None) -> int:
        import subprocess

        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            rc = self.poll()
            if rc is not None:
                return rc
            if deadline is not None and time.monotonic() >= deadline:
                raise subprocess.TimeoutExpired(
                    f"worker-{self.pid}", timeout)
            time.sleep(0.02)

    def _signal(self, sig: int) -> None:
        import signal as signal_mod

        try:
            if self._pidfd is not None and self._pidfd >= 0:
                signal_mod.pidfd_send_signal(self._pidfd, sig)
            elif self._pidfd == -1:
                os.kill(self.pid, sig)
        except OSError:
            pass  # process already reaped

    def terminate(self) -> None:
        import signal

        self._signal(signal.SIGTERM)

    def kill(self) -> None:
        import signal

        self._signal(signal.SIGKILL)


# Env vars read at jax/XLA IMPORT time: the template has already
# imported jax, so a fork can't honor a different value — such workers
# must take the fresh-interpreter path.
_IMPORT_SENSITIVE_PREFIXES = ("JAX_", "XLA_", "LIBTPU", "TPU_",
                              "PYTHONHASHSEED")


def import_sensitive_subset(env: dict) -> dict:
    return {k: v for k, v in env.items()
            if k.startswith(_IMPORT_SENSITIVE_PREFIXES)}


class WorkerFactory:
    """Handle to a running factory process; ``spawn`` forks one worker."""

    def __init__(self, proc, sock_path: str, baseline_env: dict):
        self.proc = proc
        self.sock_path = sock_path
        # The import-time-sensitive env the template booted with; spawn
        # requests demanding a different one cannot be served by fork.
        self.baseline_sensitive = import_sensitive_subset(baseline_env)

    def compatible(self, env: dict) -> bool:
        return import_sensitive_subset(env) == self.baseline_sensitive

    def spawn(self, *, addr: str | None = None,
              authkey_hex: str | None = None, env: dict,
              cwd: str | None, log_path: str | None,
              pipe_fd: int | None = None,
              timeout_s: float = 20.0) -> PidHandle:
        """Fork one worker. ``pipe_fd`` (preferred) ships a connected
        socketpair end to the child over SCM_RIGHTS — no listener
        accept or auth handshake; ``addr``/``authkey_hex`` keep the
        connect-back path for callers without fd passing."""
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.settimeout(timeout_s)
        try:
            conn.connect(self.sock_path)
            if pipe_fd is not None:
                socket.send_fds(conn, [b"FD"], [pipe_fd])
            else:
                conn.sendall(b"NO")
            _send_msg(conn, {"op": "spawn", "addr": addr,
                             "authkey": authkey_hex, "env": env,
                             "cwd": cwd, "log_path": log_path})
            reply = _recv_msg(conn)
        finally:
            conn.close()
        if not reply.get("ok"):
            raise RuntimeError(
                f"worker factory spawn failed: {reply.get('error')}")
        return PidHandle(reply["pid"])

    def alive(self) -> bool:
        return self.proc.poll() is None

    def stop(self) -> None:
        try:
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.settimeout(2.0)
            conn.connect(self.sock_path)
            conn.sendall(b"NO")  # marker: no fd rides this request
            _send_msg(conn, {"op": "exit"})
            conn.close()
        except OSError:
            pass  # factory already exited
        try:
            self.proc.wait(timeout=2.0)
        except Exception:  # noqa: BLE001
            try:
                self.proc.kill()
            except OSError:
                pass  # process exited between wait and kill


def start_factory(timeout_s: float | None = None) -> WorkerFactory:
    """Launch the template process for THIS process's workers. The
    template boots with the same CPU-pinned env as Popen'd workers."""
    import subprocess
    import tempfile

    if timeout_s is None:
        # Many daemons booting factories at once (single-box clusters)
        # serialize on the host's cores; honor the same knob that
        # governs worker startup so load-tuning covers both.
        from ray_tpu._private.config import GLOBAL_CONFIG

        timeout_s = max(
            60.0, float(GLOBAL_CONFIG.worker_startup_timeout_s) * 2)
    sock_dir = tempfile.mkdtemp(prefix="ray_tpu_factory_")
    os.chmod(sock_dir, 0o700)
    sock_path = os.path.join(sock_dir, "factory.sock")
    env = dict(os.environ)
    env["RAY_TPU_SKIP_TPU_DETECTION"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in sys.path if p] +
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    env.pop("RAY_TPU_WORKER_FACTORY_DISABLE", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.worker_factory",
         sock_path, str(os.getpid())],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + timeout_s
    line = b""
    os.set_blocking(proc.stdout.fileno(), False)
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError("worker factory exited during startup")
        try:
            chunk = proc.stdout.read()
        except OSError:
            chunk = None
        if chunk:
            line += chunk
        if b"FACTORY_READY" in line:
            return WorkerFactory(proc, sock_path, env)
        time.sleep(0.02)
    proc.kill()
    raise RuntimeError("worker factory never became ready")


if __name__ == "__main__":
    factory_main(sys.argv[1], int(sys.argv[2]))
