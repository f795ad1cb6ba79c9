"""Warm executor pool — pre-warmed children that ADOPT a task instead of
cold-starting it.

BENCH r05 measured a cold submit->first-step of 29.3s, of which 23.6s is
the training child paying `import jax` + backend init + data staging
(`launch_cold.backend_and_data_s`) — a bill charged again on every
restart-budget relaunch, preempt/resize/roll relaunch, and fleet
scale-up. This module keeps N STANDBY Python children per host that have
already prepaid exactly that bill (PAPER.md's NotebookSubmitter/
standalone mode is the precedent for pre-provisioned task processes that
adopt work instead of cold-starting):

- A standby (`python -m tony_tpu.warmpool --pool-dir ...`) imports jax,
  initializes the default backend (plus an optional user warmup hook,
  ``tony.warmpool.warmup-module`` — e.g. dataset staging to local disk),
  advertises itself in the pool directory, and blocks on a unix-socket
  control pipe.
- A task launch (runtimes/base.spawn_or_adopt) hands a ready standby the
  full task contract — env, command, cwd, log targets — over that pipe;
  the standby REPLACES its environment with the contract's, redirects
  stdout/stderr onto the container log, and execs the role's python
  entrypoint in-process via runpy, keeping the warm interpreter.
  ``jax.distributed.initialize`` is deliberately deferred to adoption
  time: coordinator/world info only exists once the gang barrier opens,
  so only the import/backend/data bill is prepaid (train/bootstrap.py's
  ``init()`` runs inside the adopted entrypoint as usual).
- A pool miss (no ready standby, non-python command, env-fingerprint
  mismatch, handshake failure) degrades to the cold ``Popen`` path —
  never to a failed launch. Container mode stays cold.

Claiming is an atomic ``os.rename`` of the standby's ready file, so
concurrent executors on one host never adopt the same standby. Standbys
run in their OWN sessions (they must survive the executor attempt that
spawned them — surviving attempts is the point), which makes reaping a
contract of its own:

- an ADOPTED child watches its adopter over the control socket and
  SIGKILLs itself on EOF — the moral equivalent of the process-group
  kill a cold in-group child would have received;
- an IDLE standby self-exits when its pool entry disappears (driver
  teardown removes the pool dir; shared-FS hosts see it too) or when the
  watched driver pid dies;
- ``WarmPool.reap()`` (driver ``stop()``) signals every same-host entry
  pid and removes the pool dir.

Executor-side accounting rides the task trace: ``child_adopted`` (pool
hit) or ``child_spawned`` with a ``warm_pool: miss`` attr; the driver
counts both into ``driver_warm_pool_{adoptions,misses}_total`` and
gauges ready standbys as ``driver_warm_pool_size`` (docs/
observability.md, docs/performance.md "Launch path").

The module is importable from the stdlib-only executor (``python -S``):
jax is imported only inside the standby's warmup.
"""

from __future__ import annotations

import argparse
import importlib
import json
import logging
import os
import re
import runpy
import select
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Any

from . import constants as c
from .conf import keys

log = logging.getLogger(__name__)

READY_SUFFIX = ".json"            # sb_<pid>.json: warmed, adoptable
CLAIMED_SUFFIX = ".json.claimed"  # mid-handshake (renamed by the claimer)
# how long a standby waits for the handshake after seeing itself claimed
# before assuming the claimer died and re-advertising (the real
# handshake follows the claim within milliseconds)
CLAIM_HANDSHAKE_S = 30.0
WARMING_SUFFIX = ".warming"       # spawned, still prepaying the bill
SOCK_SUFFIX = ".sock"
# backend-selection env the standby bakes in at warmup: a contract whose
# values differ would run on the wrong backend inside a pre-initialized
# interpreter, so a mismatch is a pool MISS, not a wrong adoption
ENV_FINGERPRINT_KEYS = ("JAX_PLATFORMS", "XLA_FLAGS", "TPU_CHIPS_PER_HOST_BOUNDS")
# how long a post-adoption replenishment waits before spawning the
# replacement standby: an immediate spawn's jax import + warmup competes
# with the freshly ADOPTED child's own first-step compile for host CPU
# (measured +3.5s submit->first-step on a 2-core host). The pool refills
# BETWEEN launches, not during them. Env-overridable (tests set 0).
REPLENISH_DELAY_ENV = "TONY_WARMPOOL_REPLENISH_DELAY_S"


def replenish_delay_s() -> float:
    try:
        return max(0.0, float(os.environ.get(REPLENISH_DELAY_ENV, "10")))
    except ValueError:
        return 10.0


# raw shell syntax the in-process runner cannot honor (plain $VAR
# expansion it CAN — expanded against the contract env at adoption)
_SHELL_META = re.compile(r"[|&;<>`]|\$\(")
_ENV_ASSIGN = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*=")
_PY_SKIP_FLAGS = frozenset({"-u", "-E", "-s", "-S", "-O", "-OO", "-B", "-I"})
_PY_ARG_FLAGS = frozenset({"-X", "-W"})


def _pid_alive(pid: int) -> bool:
    """Liveness that treats a ZOMBIE as dead: a long-lived spawner
    (the driver seeding the pool) holds its standbys as unreaped
    children, and a kill(pid, 0) would call the corpse alive."""
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    try:
        with open(f"/proc/{pid}/stat") as f:
            # field 3 is the state letter; the comm field before it may
            # itself contain spaces/parens, so split after the LAST ')'
            return f.read().rpartition(")")[2].split()[0] != "Z"
    except (OSError, IndexError):
        return True
    return True


def _is_standby_pid(pid: int) -> bool:
    """Does this pid still belong to a warm-pool process? Entry pids are
    only ever signalled after this check: a standby that died and had
    its pid RECYCLED by an unrelated service must not be killed on the
    strength of a stale pool file (host-level pools live for days).
    Adopted children keep their original argv in /proc, so the check
    stays true across adoption."""
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return b"tony_tpu.warmpool" in f.read()
    except OSError:
        return False


def _write_json_atomic(path: Path, payload: dict) -> None:
    tmp = Path(str(path) + ".tmp")
    tmp.write_text(json.dumps(payload))
    os.replace(tmp, path)


def _unlink(path: Path) -> None:
    try:
        path.unlink()
    except OSError:
        pass


# --------------------------------------------------------------- command parse
def parse_python_command(command: str) -> dict[str, Any] | None:
    """Is this role command a single python invocation the standby can run
    in-process? Returns ``{"module"|"script", "args", "env"}`` or None.

    Adoptable: ``[VAR=val ...] python[3[.x]] [-u -X... -W...] (-m mod |
    script.py) args...``. Plain ``$VAR`` references are fine (expanded
    against the contract env at adoption, mirroring what ``bash -c``
    would have done); pipelines/compound commands/substitutions are not
    — those genuinely need a shell and stay on the cold path."""
    if _SHELL_META.search(command):
        return None
    try:
        tokens = shlex.split(command)
    except ValueError:
        return None
    env: dict[str, str] = {}
    i = 0
    while i < len(tokens) and _ENV_ASSIGN.match(tokens[i]):
        k, _, v = tokens[i].partition("=")
        env[k] = v
        i += 1
    if i >= len(tokens):
        return None
    prog = os.path.basename(tokens[i])
    if not (prog == "python" or prog.startswith("python3")
            or tokens[i] == sys.executable):
        return None
    i += 1
    module = script = None
    while i < len(tokens):
        t = tokens[i]
        if t == "-m":
            if i + 1 >= len(tokens):
                return None
            module = tokens[i + 1]
            i += 2
            break
        if t in _PY_SKIP_FLAGS:
            i += 1
            continue
        if t in _PY_ARG_FLAGS:
            i += 2
            continue
        if t.startswith("-"):       # -c payloads and unknown flags: cold
            return None
        script = t
        i += 1
        break
    if module is None and script is None:
        return None
    return {"module": module, "script": script, "args": tokens[i:],
            "env": env}


def env_compatible(info: dict, contract_env: dict) -> bool:
    """May a standby described by ``info`` (its ready file) run a task
    with ``contract_env``? Only standbys that actually warmed a backend
    are fingerprint-bound; a skip-warmup standby (tests) is a blank
    interpreter and takes anything."""
    if "warmup" not in info:
        return True
    fp = info.get("env_fingerprint") or {}
    for key in ENV_FINGERPRINT_KEYS:
        if str(fp.get(key, "") or "") != str(contract_env.get(key, "") or ""):
            return False
    return True


# ------------------------------------------------------------- adopted handle
class AdoptedChild:
    """Popen-shaped handle on a standby that adopted this task.

    The adopter is NOT the standby's parent, so exit status travels over
    the control socket (``{"exit": code}`` sent just before the standby
    ``os._exit``s). EOF without a report means the standby was killed
    outright — reported as EXIT_KILLED, the same code the provisioner's
    group SIGKILL gives a cold child. Signals go by pid."""

    def __init__(self, pid: int, sock: socket.socket,
                 warmed_s: float = 0.0):
        self.pid = pid
        self.returncode: int | None = None
        self.warmed_s = warmed_s
        self._sock = sock
        self._sock.setblocking(False)
        self._buf = b""
        self._eof = False

    def poll(self) -> int | None:
        if self.returncode is not None:
            return self.returncode
        while not self._eof:
            try:
                chunk = self._sock.recv(4096)
            except (BlockingIOError, InterruptedError):
                break
            except OSError:
                self._eof = True
                break
            if not chunk:
                self._eof = True
                break
            self._buf += chunk
        for line in self._buf.split(b"\n"):
            if not line.strip():
                continue
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            if isinstance(msg, dict) and isinstance(msg.get("exit"), int):
                self.returncode = msg["exit"]
        if self.returncode is None and self._eof and not _pid_alive(self.pid):
            self.returncode = c.EXIT_KILLED
        return self.returncode

    def wait(self, timeout: float | None = None) -> int:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            rc = self.poll()
            if rc is not None:
                return rc
            if deadline is not None and time.monotonic() >= deadline:
                raise subprocess.TimeoutExpired(
                    f"adopted:{self.pid}", timeout)
            if self._eof:
                # the socket is gone but the pid lives (a child that
                # closed inherited fds): select on an EOF'd socket
                # returns readable instantly — poll the pid instead of
                # busy-spinning a core
                time.sleep(0.2)
                continue
            try:
                select.select([self._sock], [], [], 0.2)
            except OSError:
                time.sleep(0.05)

    def terminate(self) -> None:
        self._signal(signal.SIGTERM)

    def kill(self) -> None:
        self._signal(signal.SIGKILL)

    def _signal(self, sig: int) -> None:
        try:
            os.kill(self.pid, sig)
        except (ProcessLookupError, PermissionError):
            pass


# --------------------------------------------------------------------- pool
def _driver_json_pid(path: str | Path) -> int:
    """The driver pid advertised by a driver.json file, usable as a
    liveness watch ONLY when the driver runs on this host (loopback RPC
    endpoint) — a remote pid number would alias an unrelated local
    process."""
    try:
        info = json.loads(Path(path).read_text())
    except (OSError, ValueError):
        return 0
    if info.get("host") not in ("127.0.0.1", "localhost", "::1"):
        return 0
    pid = info.get("pid")
    return pid if isinstance(pid, int) and pid > 0 else 0


def _driver_watch_pid(job_dir: str) -> int:
    """The driver pid from the job dir's driver.json (see
    ``_driver_json_pid``)."""
    if not job_dir:
        return 0
    return _driver_json_pid(Path(job_dir) / c.DRIVER_INFO_FILE)


def count_ready(pool_dir: str | Path | None) -> int:
    """Live, unclaimed standbys in the pool (drives the
    ``driver_warm_pool_size`` gauge)."""
    if not pool_dir:
        return 0
    n = 0
    try:
        entries = sorted(Path(pool_dir).glob("sb_*" + READY_SUFFIX))
    except OSError:
        return 0
    for path in entries:
        try:
            info = json.loads(path.read_text())
        except (OSError, ValueError):
            continue
        pid = info.get("pid")
        if isinstance(pid, int) and _pid_alive(pid) and _is_standby_pid(pid):
            n += 1
    return n


class WarmPool:
    """Host-side view of one pool directory: spawn standbys up to the
    configured size, adopt from it, reap it at teardown."""

    def __init__(self, pool_dir: str | Path, size: int,
                 warmup_module: str = "", watch_pid: int = 0,
                 spawn_env: dict[str, str] | None = None,
                 driver_json: str = "", outage_grace_s: float = 30.0):
        self.dir = Path(pool_dir)
        self.size = int(size)
        self.warmup_module = warmup_module
        self.watch_pid = int(watch_pid)
        self.spawn_env = dict(spawn_env or {})
        # driver-outage tolerance for per-job pools: when the watched
        # driver pid dies, standbys re-resolve this driver.json for the
        # RECOVERED driver's pid for outage_grace_s before self-reaping
        # — a recovered driver finds its pool warm instead of cold
        self.driver_json = str(driver_json or "")
        self.outage_grace_s = float(outage_grace_s)
        # Popen handles of standbys THIS process spawned: polled on every
        # scan so exited standbys are reaped instead of lingering as
        # zombies under a long-lived spawner (the driver)
        self._procs: list[subprocess.Popen] = []

    # -------------------------------------------------------- construction
    @classmethod
    def from_conf(cls, conf, job_dir: str,
                  spawn_env: dict[str, str] | None = None) -> "WarmPool | None":
        """None when the pool is off (size<=0) or has nowhere to live."""
        if conf is None:
            return None
        try:
            size = conf.get_int(keys.WARMPOOL_SIZE, 0)
        except (TypeError, ValueError):
            return None
        if size <= 0:
            return None
        pool_dir = str(conf.get(keys.WARMPOOL_DIR, "") or "")
        watch_pid = 0
        driver_json = ""
        if not pool_dir:
            if not job_dir:
                return None
            pool_dir = os.path.join(str(job_dir), c.WARMPOOL_DIR_NAME)
            # per-JOB pool: standbys die with the job's driver; an
            # explicit tony.warmpool.dir is host-level capacity shared
            # across submits and must outlive any one driver. The
            # driver.json path lets standbys survive a driver RESTART:
            # they re-resolve the recovered driver's pid from it for the
            # outage grace before self-reaping.
            watch_pid = _driver_watch_pid(str(job_dir))
            driver_json = os.path.join(str(job_dir), c.DRIVER_INFO_FILE)
        try:
            grace_s = conf.get_int(keys.TASK_DRIVER_OUTAGE_GRACE_MS,
                                   30000) / 1000
        except (TypeError, ValueError):
            grace_s = 30.0
        return cls(
            pool_dir, size,
            warmup_module=str(conf.get(keys.WARMPOOL_WARMUP_MODULE, "") or ""),
            watch_pid=watch_pid,
            spawn_env=spawn_env,
            driver_json=driver_json,
            outage_grace_s=grace_s,
        )

    @classmethod
    def from_context(cls, ctx) -> "WarmPool | None":
        """Pool for an executor-side TaskContext (container mode stays
        cold — the adapter never calls this on that branch)."""
        job_dir = (ctx.base_child_env or {}).get(c.ENV_JOB_DIR, "")
        return cls.from_conf(ctx.conf, job_dir)

    # ------------------------------------------------------------ lifecycle
    def _entries(self) -> list[tuple[Path, dict]]:
        out = []
        try:
            paths = sorted(self.dir.iterdir())
        except OSError:
            return out
        for path in paths:
            if not path.name.startswith("sb_"):
                continue
            if path.name.endswith((".tmp", ".log", SOCK_SUFFIX)):
                continue
            try:
                info = json.loads(path.read_text())
            except (OSError, ValueError):
                info = {}
            out.append((path, info if isinstance(info, dict) else {}))
        return out

    def _live_count(self) -> int:
        """Ready + still-warming standbys; stale entries (dead pids) are
        swept on the way."""
        self._procs = [p for p in self._procs if p.poll() is None]
        n = 0
        for path, info in self._entries():
            pid = info.get("pid")
            alive = (isinstance(pid, int) and _pid_alive(pid)
                     and _is_standby_pid(pid))
            if path.name.endswith(CLAIMED_SUFFIX):
                if not alive:
                    _unlink(path)
                continue        # mid-adoption: already promised to a task
            if not alive:
                _unlink(path)
                if isinstance(pid, int):
                    _unlink(self.dir / f"sb_{pid}{SOCK_SUFFIX}")
                continue
            n += 1
        return n

    def ensure(self) -> int:
        """Top the pool up to ``size`` standbys; returns how many were
        spawned. Cheap when the pool is full (one directory scan).
        Serialized host-wide with an flock: a gang's co-hosted
        executors all ensure() at startup, and an unserialized
        scan-then-spawn would let each of them count the deficit before
        any warming marker lands — N executors × size over-spawned
        jax-loaded interpreters with nothing to ever trim them."""
        import fcntl

        self.dir.mkdir(parents=True, exist_ok=True)
        with open(self.dir / ".ensure.lock", "w") as lock:
            try:
                fcntl.flock(lock, fcntl.LOCK_EX)
            except OSError:
                pass        # no flock (exotic FS): racy over-spawn beats none
            # one scan up front: spawn_one writes a warming marker that
            # _live_count would immediately re-count
            needed = self.size - self._live_count()
            for _ in range(max(0, needed)):
                self.spawn_one()
        return max(0, needed)

    def spawn_one(self) -> int:
        """Start one standby in its own session; returns its pid. The
        warming marker is written here so a concurrent ensure() counts
        it before the standby finishes booting."""
        self.dir.mkdir(parents=True, exist_ok=True)
        argv = [sys.executable, "-m", "tony_tpu.warmpool",
                "--pool-dir", str(self.dir)]
        if self.warmup_module:
            argv += ["--warmup-module", self.warmup_module]
        if self.watch_pid:
            argv += ["--watch-pid", str(self.watch_pid)]
        if self.driver_json:
            argv += ["--driver-json", self.driver_json,
                     "--outage-grace-s", str(self.outage_grace_s)]
        env = {**os.environ, **self.spawn_env}
        # the standby must import tony_tpu no matter the spawner's cwd
        # (the executor may run from a localized work dir)
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = (
            pkg_root + ((os.pathsep + env["PYTHONPATH"])
                        if env.get("PYTHONPATH") else ""))
        log_path = self.dir / "spawn.log"
        # NOTE: no preexec_fn — forking python code from the driver's /
        # executor's threaded process can deadlock the child before
        # exec; the standby renices ITSELF first thing in standby_main
        with open(log_path, "ab") as out:
            proc = subprocess.Popen(
                argv, env=env, stdout=out, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        self._procs.append(proc)
        _write_json_atomic(
            self.dir / f"sb_{proc.pid}{WARMING_SUFFIX}",
            {"pid": proc.pid, "host": socket.gethostname(),
             "t": time.time()})
        log.info("spawned warm standby pid=%d in %s", proc.pid, self.dir)
        return proc.pid

    # ------------------------------------------------------------- adoption
    def adopt(self, command: str, contract_env: dict[str, str],
              cwd: str | None = None) -> AdoptedChild | None:
        """Claim a ready standby and hand it the task contract. None on
        any miss (no standby, non-adoptable command, env mismatch,
        handshake failure) — the caller falls back to the cold spawn."""
        spec = parse_python_command(command)
        if spec is None:
            log.info("warm pool miss: command is not a single python "
                     "invocation")
            return None
        try:
            ready = sorted(self.dir.glob("sb_*" + READY_SUFFIX))
        except OSError:
            return None
        for path in ready:
            try:
                info = json.loads(path.read_text())
            except (OSError, ValueError):
                continue
            pid = info.get("pid")
            if (not isinstance(pid, int) or not _pid_alive(pid)
                    or not _is_standby_pid(pid)):
                _unlink(path)
                continue
            if not env_compatible(info, contract_env):
                log.info("warm pool: standby %d env fingerprint mismatch; "
                         "skipping", pid)
                continue
            claimed = Path(str(path) + ".claimed")
            try:
                os.rename(path, claimed)
            except OSError:
                continue        # another executor won the claim race
            try:
                child = self._handshake(info, command, contract_env, cwd)
            except Exception as e:
                log.warning("adoption of standby %d failed (%s); trying "
                            "the next one", pid, e)
                if _is_standby_pid(pid):    # never a recycled pid
                    try:
                        os.kill(pid, signal.SIGKILL)
                    except OSError:
                        pass
                _unlink(claimed)
                _unlink(self.dir / f"sb_{pid}{SOCK_SUFFIX}")
                continue
            log.info("adopted warm standby pid=%d (warmed %.1fs ago bill "
                     "prepaid in %.1fs)", pid,
                     time.time() - float(info.get("created", time.time())),
                     child.warmed_s)
            return child
        log.info("warm pool miss: no ready standby in %s", self.dir)
        return None

    def _handshake(self, info: dict, command: str,
                   contract_env: dict[str, str],
                   cwd: str | None) -> AdoptedChild:
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(5.0)
        sock.connect(info["sock"])
        contract = {
            "command": command,
            "env": {str(k): str(v) for k, v in contract_env.items()},
            "cwd": cwd,
            "stdout_path": _fd_target(1),
            "stderr_path": _fd_target(2),
        }
        sock.sendall(json.dumps(contract).encode() + b"\n")
        sock.settimeout(15.0)
        # a fast child can exit before this read: the ack and the exit
        # report may arrive together — only the FIRST line is the ack,
        # the rest belongs to the AdoptedChild's stream
        line, rest = _recv_line(sock)
        ack = json.loads(line)
        if not (isinstance(ack, dict) and ack.get("ok")):
            raise RuntimeError(f"standby refused the contract: {ack}")
        sock.settimeout(None)
        child = AdoptedChild(int(info["pid"]), sock,
                             warmed_s=float(info.get("warmed_s", 0.0)))
        child._buf = rest
        return child

    # ----------------------------------------------------------------- reap
    def reap(self, grace_s: float = 2.0) -> None:
        """Teardown: signal every same-host entry pid (SIGTERM, then
        SIGKILL past the grace) and remove the pool directory. Entries
        from OTHER hosts (shared FS) only lose their files — their
        standbys notice the missing entry and self-exit; their pid
        numbers mean nothing here and are never signalled."""
        me = socket.gethostname()
        pids = []
        for path, info in self._entries():
            pid = info.get("pid")
            host = info.get("host", me)
            if (isinstance(pid, int) and host == me and _pid_alive(pid)
                    and _is_standby_pid(pid)):
                pids.append(pid)
            _unlink(path)
        for pid in pids:
            try:
                os.kill(pid, signal.SIGTERM)
            except OSError:
                pass
        deadline = time.monotonic() + grace_s
        while pids and time.monotonic() < deadline:
            pids = [p for p in pids if _pid_alive(p)]
            if pids:
                time.sleep(0.05)
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        for p in self._procs:       # reap our own corpses
            try:
                p.wait(timeout=1.0)
            except Exception:
                pass
        self._procs.clear()
        import shutil

        shutil.rmtree(self.dir, ignore_errors=True)


def _fd_target(fd: int) -> str | None:
    """Where this process's fd points, if it is a real file the standby
    can re-open (the container log the provisioner gave the executor).
    Pipes/sockets/ttys return None and the adopted child keeps writing
    to its standby log."""
    try:
        target = os.readlink(f"/proc/self/fd/{fd}")
    except OSError:
        return None
    return target if target.startswith("/") and os.path.exists(target) else None


def _recv_line(sock: socket.socket) -> tuple[bytes, bytes]:
    """Read up to the first newline; returns (line, leftover bytes that
    arrived with it)."""
    buf = b""
    while b"\n" not in buf:
        chunk = sock.recv(4096)
        if not chunk:
            break
        buf += chunk
    if not buf:
        raise RuntimeError("peer closed the control pipe mid-handshake")
    line, _, rest = buf.partition(b"\n")
    return line, rest


# ------------------------------------------------------------- standby process
def _default_warmup() -> dict:
    """The prepaid bill: import jax, initialize the default backend, and
    push one tiny jitted dispatch through it so the client, compiler
    plumbing, and transfer path are all live before adoption."""
    from .utils.jaxenv import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    jax.jit(lambda x: x + 1)(jnp.zeros((8,), jnp.float32)).block_until_ready()
    return {"devices": len(devices), "backend": jax.default_backend()}


_EXITING = False    # normal-exit fence for the adopter watchdog


def _watch_adopter(conn: socket.socket) -> None:
    """EOF on the control pipe means the adopter (executor) is gone: die
    the way a cold in-group child would have died with it. The fence
    keeps a normal exit's own socket shutdown from reading as adopter
    death."""
    try:
        while True:
            data = conn.recv(1)
            if not data:
                break
    except OSError:
        pass
    if _EXITING:
        return
    log.error("adopter vanished; killing the adopted child")
    os.kill(os.getpid(), signal.SIGKILL)


def _run_entrypoint(spec: dict) -> int:
    """Run the parsed python invocation in-process as ``__main__``."""
    os.environ.update(spec.get("env") or {})
    try:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
    except ValueError:
        pass
    # the standby warmed at background priority (standby_main); the
    # ADOPTED child is foreground work again. Lowering niceness needs
    # privilege (root / CAP_SYS_NICE — the usual TPU-VM runtime user);
    # elsewhere the child stays at nice 10, which only matters on an
    # oversubscribed host.
    try:
        os.setpriority(os.PRIO_PROCESS, 0, 0)
    except (OSError, AttributeError):
        pass
    sys.argv = [spec["module"] or spec["script"]] + list(spec["args"])
    try:
        if spec["module"]:
            runpy.run_module(spec["module"], run_name="__main__",
                             alter_sys=True)
        else:
            script = spec["script"]
            # a real `python script.py` puts the script's dir on sys.path
            sys.path.insert(0, os.path.dirname(os.path.abspath(script)))
            runpy.run_path(script, run_name="__main__")
        return 0
    except SystemExit as e:
        if e.code is None:
            return 0
        if isinstance(e.code, int):
            return e.code
        print(e.code, file=sys.stderr)
        return 1
    except BaseException:
        import traceback

        traceback.print_exc()
        return 1


def _redirect_output(stdout_path: str | None, stderr_path: str | None) -> None:
    """dup2 the task's log targets over the standby's fds so the adopted
    child's output lands where the cold child's would have."""
    for fd, path in ((1, stdout_path), (2, stderr_path)):
        if not path:
            continue
        try:
            target = os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT,
                             0o644)
            os.dup2(target, fd)
            os.close(target)
        except OSError as e:
            log.warning("could not redirect fd %d to %s: %s", fd, path, e)


def _serve_adoption(conn: socket.socket, pool_dir: Path, stem: str) -> int:
    """The standby's second life: apply the contract, become the task."""
    conn.settimeout(30.0)
    try:
        line, _ = _recv_line(conn)
        contract = json.loads(line)
        env = contract.get("env") or {}
        os.environ.clear()
        os.environ.update({str(k): str(v) for k, v in env.items()})
        cwd = contract.get("cwd")
        if cwd:
            os.chdir(cwd)
        _redirect_output(contract.get("stdout_path"),
                         contract.get("stderr_path"))
        # $VAR references the shell would have expanded are expanded here
        # against the freshly-applied contract env
        spec = parse_python_command(os.path.expandvars(contract["command"]))
        if spec is None:
            raise ValueError("command is not adoptable")
    except Exception as e:
        log.exception("adoption contract failed")
        try:
            conn.sendall(json.dumps({"ok": False, "error": str(e)}).encode()
                         + b"\n")
        except OSError:
            pass
        # env is possibly half-applied: this interpreter cannot go back
        # in the pool
        _cleanup_standby_files(pool_dir, stem)
        return 1
    conn.sendall(json.dumps({"ok": True, "pid": os.getpid()}).encode()
                 + b"\n")
    conn.settimeout(None)
    _cleanup_standby_files(pool_dir, stem)
    threading.Thread(target=_watch_adopter, args=(conn,),
                     name="adopter-watch", daemon=True).start()
    code = _run_entrypoint(spec)
    global _EXITING
    _EXITING = True
    try:
        sys.stdout.flush()
        sys.stderr.flush()
    except OSError:
        pass
    try:
        conn.sendall(json.dumps({"exit": code}).encode() + b"\n")
        conn.shutdown(socket.SHUT_RDWR)
        conn.close()
    except OSError:
        pass
    # _exit, not sys.exit: the entrypoint ran (and flushed) as __main__;
    # a second trip through this module's frames must not re-raise
    os._exit(code)


def _cleanup_standby_files(pool_dir: Path, stem: str) -> None:
    for suffix in (READY_SUFFIX, CLAIMED_SUFFIX, WARMING_SUFFIX, SOCK_SUFFIX):
        _unlink(pool_dir / (stem + suffix))


def standby_main(argv: list[str] | None = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s standby %(name)s: %(message)s",
    )
    parser = argparse.ArgumentParser(description="tony-tpu warm standby")
    parser.add_argument("--pool-dir", required=True)
    parser.add_argument("--warmup-module", default="")
    parser.add_argument("--watch-pid", type=int, default=0)
    parser.add_argument(
        "--driver-json", default="",
        help="path to the job's driver.json: when the watched pid dies, "
             "re-resolve a RECOVERED driver's pid from it for the outage "
             "grace before self-reaping (keeps the pool warm across a "
             "driver restart)")
    parser.add_argument("--outage-grace-s", type=float, default=30.0)
    args = parser.parse_args(argv)

    # a standby's warmup is BACKGROUND work and must yield the CPU to
    # live tasks (the replenish delay is the primary defense; this
    # covers seeding during first launches). Self-applied — a spawner-
    # side preexec_fn would fork python code under the driver's threads.
    try:
        os.nice(10)
    except OSError:
        pass
    pool_dir = Path(args.pool_dir)
    pool_dir.mkdir(parents=True, exist_ok=True)
    me = os.getpid()
    stem = f"sb_{me}"
    sock_path = pool_dir / (stem + SOCK_SUFFIX)
    ready_path = pool_dir / (stem + READY_SUFFIX)
    claimed_path = pool_dir / (stem + CLAIMED_SUFFIX)
    _unlink(sock_path)
    listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    listener.bind(str(sock_path))
    listener.listen(1)

    t0 = time.monotonic()
    info: dict[str, Any] = {
        "pid": me, "host": socket.gethostname(),
        "sock": str(sock_path), "created": time.time(),
    }
    if not os.environ.get(c.TEST_WARMPOOL_SKIP_WARMUP):
        try:
            info["warmup"] = _default_warmup()
            info["env_fingerprint"] = {
                k: os.environ.get(k, "") for k in ENV_FINGERPRINT_KEYS}
        except Exception as e:
            # an adoptable blank interpreter beats no standby at all
            log.warning("default warmup failed: %s", e)
            info["warmup_error"] = str(e)
    if args.warmup_module:
        try:
            mod = importlib.import_module(args.warmup_module)
            fn = getattr(mod, "warmup", None)
            if callable(fn):
                fn()
            info["warmup_module"] = args.warmup_module
        except Exception as e:
            log.warning("warmup module %s failed: %s", args.warmup_module, e)
            info["warmup_module_error"] = str(e)
    info["warmed_s"] = round(time.monotonic() - t0, 3)
    _write_json_atomic(ready_path, info)
    _unlink(pool_dir / (stem + WARMING_SUFFIX))
    log.info("standby %d ready in %s (warmed in %.1fs)", me, pool_dir,
             info["warmed_s"])

    listener.settimeout(1.0)
    conn = None
    claim_seen_t: float | None = None
    outage_t: float | None = None       # watched-driver death instant
    while conn is None:
        try:
            conn, _ = listener.accept()
        except socket.timeout:
            # self-reap: pool entry removed (teardown swept the dir) or
            # the watched driver died without a clean stop
            if not (ready_path.exists() or claimed_path.exists()):
                log.info("pool entry gone; standby %d exiting", me)
                _unlink(sock_path)
                return 0
            # claim-abandonment recovery: an adopter that died between
            # its claim rename and the handshake would otherwise park
            # this standby (and leak it in host-level pools, where no
            # driver reap runs) — put the entry back up for adoption
            if claimed_path.exists() and not ready_path.exists():
                if claim_seen_t is None:
                    claim_seen_t = time.monotonic()
                elif time.monotonic() - claim_seen_t > CLAIM_HANDSHAKE_S:
                    log.warning(
                        "claim abandoned (no handshake in %.0fs); "
                        "standby %d re-advertising", CLAIM_HANDSHAKE_S, me)
                    try:
                        os.rename(claimed_path, ready_path)
                    except OSError:
                        _cleanup_standby_files(pool_dir, stem)
                        return 0
                    claim_seen_t = None
            else:
                claim_seen_t = None
            if args.watch_pid and not _pid_alive(args.watch_pid):
                # driver-outage grace: a SIGKILLed driver's recovered
                # successor rewrites driver.json with ITS pid — adopt it
                # as the new watch target so the pool stays warm across
                # the restart; self-reap only once the grace runs dry
                new_pid = (_driver_json_pid(args.driver_json)
                           if args.driver_json else 0)
                if (new_pid and new_pid != args.watch_pid
                        and _pid_alive(new_pid)):
                    log.warning(
                        "watched driver %d died; re-watching recovered "
                        "driver %d (driver.json)", args.watch_pid, new_pid)
                    args.watch_pid = new_pid
                    outage_t = None
                    continue
                if outage_t is None and args.driver_json:
                    outage_t = time.monotonic()
                    log.warning(
                        "watched pid %d gone; standby %d riding the "
                        "%.1fs driver-outage grace", args.watch_pid, me,
                        args.outage_grace_s)
                if (outage_t is not None and time.monotonic() - outage_t
                        <= args.outage_grace_s):
                    continue
                log.info("watched pid %d gone; standby %d exiting",
                         args.watch_pid, me)
                _cleanup_standby_files(pool_dir, stem)
                return 0
            outage_t = None
        except OSError as e:
            log.error("control socket failed: %s", e)
            _cleanup_standby_files(pool_dir, stem)
            return 1
    listener.close()
    return _serve_adoption(conn, pool_dir, stem)


if __name__ == "__main__":
    sys.exit(standby_main())
