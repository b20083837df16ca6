"""ProcCluster — REAL multi-process cluster harness for crash chaos.

Everything the in-process LocalCluster cannot prove, this can: it boots
actual ``daemons/{metad,storaged,graphd}.py`` SUBPROCESSES over TCP (the
``use_tcp=True`` plumbing the daemons already serve), so a "kill" is a
SIGKILL delivered to a process with a half-written WAL and a warm page
cache — not a thread politely unwinding.  The kill-matrix chaos suite
(tests/test_proc_chaos.py, scripts/chaos.sh) drives it through five
primitives:

    kill(name, sig)        SIGKILL/SIGTERM one daemon, wait for exit
    restart(name)          respawn with the SAME argv (ports, data dirs)
    wait_healthy(name)     poll the daemon's /healthz (the PR 5 probe)
                           until 200 — THE wait-for-recovery gate
    metrics(name)          GET /metrics (Prometheus text) for assertions
    events(name)           GET /events — wal.truncated / node.recovered

Recovery contract the suite asserts (docs/durability.md crash matrix):
after any SIGKILL + restart, a node recovers to the last acked raft
entry — the CRC'd WAL (kvstore/wal.py v2) truncates unverifiable
frames instead of replaying garbage, the disk engine recovers to its
last committed MANIFEST, and clients converge through leader-cache
invalidation + re-discovery with every query ending in success, a typed
partial, or a typed error within its deadline.

Stderr of every daemon streams to ``<run_dir>/<name>.log`` so a failed
scenario is diagnosable post-mortem.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional


class RoundRobinClient:
    """Thread-safe round-robin façade over N graphd clients — the
    horizontal-scale tier's balancer stand-in (ROADMAP item 3: N
    stateless graphd instances sharing one storaged/device runtime
    behind a balancer).  Statements rotate across the front ends;
    per-statement affinity is irrelevant because graphd is stateless
    between statements EXCEPT session state (USE <space>), so
    ``use(space)`` pins the space on every backend first."""

    def __init__(self, clients: List):
        if not clients:
            raise ValueError("RoundRobinClient needs >= 1 client")
        self._clients = list(clients)
        self._lock = threading.Lock()
        self._i = 0

    def use(self, space: str) -> None:
        for cl in self._clients:
            r = cl.execute(f"USE {space}")
            if not r.ok():
                raise RuntimeError(f"USE {space}: {r.error_msg}")

    def pick(self):
        with self._lock:
            cl = self._clients[self._i % len(self._clients)]
            self._i += 1
        return cl

    def execute(self, stmt: str):
        return self.pick().execute(stmt)


def _free_port() -> int:
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def _repo_root() -> str:
    # nebula_tpu/tools/proc_cluster.py -> repo root (parent of the pkg)
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


class ProcDaemon:
    """One daemon subprocess: its argv (for identical restarts), ports,
    and log file."""

    def __init__(self, name: str, argv: List[str], port: int,
                 ws_port: int, log_path: str, env: Dict[str, str]):
        self.name = name
        self.argv = argv
        self.port = port
        self.ws_port = ws_port
        self.log_path = log_path
        self.env = env
        self.proc: Optional[subprocess.Popen] = None

    # ------------------------------------------------------- lifecycle
    def spawn(self) -> None:
        log = open(self.log_path, "ab")
        try:
            self.proc = subprocess.Popen(
                self.argv, stdout=log, stderr=log,
                env=self.env, cwd=_repo_root(),
                start_new_session=True)   # its own group: our SIGKILL
        finally:                          # never leaks to the test runner
            log.close()

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def kill(self, sig: int = signal.SIGKILL, wait_s: float = 10.0) -> None:
        if self.proc is None:
            return
        try:
            self.proc.send_signal(sig)
        except ProcessLookupError:
            pass
        try:
            self.proc.wait(timeout=wait_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=wait_s)

    # ------------------------------------------------------- ops plane
    def _http(self, path: str, timeout: float = 2.0) -> str:
        url = f"http://127.0.0.1:{self.ws_port}{path}"
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.read().decode()

    def healthz(self, timeout: float = 2.0) -> Optional[dict]:
        """Parsed /healthz body, or None when unreachable.  A 503 still
        returns the body (checks say WHICH probe failed)."""
        try:
            return json.loads(self._http("/healthz", timeout))
        except urllib.error.HTTPError as e:
            try:
                return json.loads(e.read().decode())
            except Exception:      # noqa: BLE001 — non-JSON error body
                return None
        except Exception:          # noqa: BLE001 — daemon down
            return None

    def metrics(self, timeout: float = 5.0) -> str:
        return self._http("/metrics", timeout)

    def status(self, timeout: float = 5.0) -> dict:
        """Parsed /status — on a storaged its ``device`` field is the
        platform / device_kind / device_count of the runtime that
        served (None before the first device request)."""
        return json.loads(self._http("/status", timeout))

    def events(self, timeout: float = 5.0) -> List[dict]:
        return json.loads(self._http("/events", timeout)).get("events", [])

    def tail_log(self, n: int = 40) -> str:
        try:
            with open(self.log_path) as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""


class ProcCluster:
    """metad + N storaged + graphd as real subprocesses over TCP.

    ``run_dir`` holds every daemon's data/WAL directories and logs —
    pass a pytest tmp_path.  ``extra_flags`` are appended as ``--flag
    name=value`` to every daemon (chaos suites shrink heartbeat /
    election timers there).  ``storage_backend="cpu"`` by default keeps
    subprocess boot lean (no jax import on the storaged); pass "tpu"
    to exercise device serving across the process boundary.

    ``device_env`` is laid over the inherited environment of the
    STORAGED processes — the only daemons that touch jax.  The default
    forces CPU jax (tier-1, chaos and the test-driven bench legs run
    from parents that already hold whatever accelerator exists).  To
    put storaged on the chip pass ``{"JAX_PLATFORMS": "tpu"}`` from a
    parent that has NOT initialised jax: a chip belongs to one process,
    so it is one storaged per chip.  metad and graphd are jax-free and
    always get ``JAX_PLATFORMS=cpu``."""

    BOOT_TIMEOUT_S = 60.0
    CPU_DEVICE_ENV = {"JAX_PLATFORMS": "cpu"}

    def __init__(self, run_dir: str, num_storage: int = 1,
                 storage_backend: str = "cpu",
                 extra_flags: Optional[Dict[str, object]] = None,
                 start: bool = True,
                 device_env: Optional[Dict[str, str]] = None):
        self.run_dir = os.path.abspath(run_dir)
        os.makedirs(self.run_dir, exist_ok=True)
        self.daemons: Dict[str, ProcDaemon] = {}
        flags = dict(extra_flags or {})
        flags.setdefault("storage_backend", storage_backend)
        # fast recovery convergence: a restarted daemon re-registers /
        # refreshes within a couple of seconds instead of minutes
        flags.setdefault("heartbeat_interval_secs", 1)
        flags.setdefault("load_data_interval_secs", 2)
        flag_args: List[str] = []
        for k, v in flags.items():
            flag_args += ["--flag", f"{k}={v}"]

        env = dict(os.environ)
        env["PYTHONPATH"] = _repo_root() + os.pathsep + \
            env.get("PYTHONPATH", "")
        env.setdefault("PYTHONUNBUFFERED", "1")
        storaged_env = {**env, **(self.CPU_DEVICE_ENV if device_env is None
                                  else device_env)}
        env.update(self.CPU_DEVICE_ENV)
        # kept for add_graphd: extra front ends inherit the cluster's
        # flag set (overridable per instance)
        self._flag_args = list(flag_args)
        self._env = env

        meta_port, meta_ws = _free_port(), _free_port()
        self.meta_addr = f"127.0.0.1:{meta_port}"
        self._register("metad", [
            sys.executable, "-m", "nebula_tpu.daemons.metad",
            "--local_ip", "127.0.0.1", "--port", str(meta_port),
            "--ws_http_port", str(meta_ws),
            "--meta_server_addrs", self.meta_addr,
            "--data_path", os.path.join(self.run_dir, "metad"),
        ] + flag_args, meta_port, meta_ws, env)

        self.storage_names: List[str] = []
        for i in range(num_storage):
            port, ws = _free_port(), _free_port()
            name = f"storaged{i}"
            self.storage_names.append(name)
            self._register(name, [
                sys.executable, "-m", "nebula_tpu.daemons.storaged",
                "--local_ip", "127.0.0.1", "--port", str(port),
                "--ws_http_port", str(ws),
                "--meta_server_addrs", self.meta_addr,
                "--data_path", os.path.join(self.run_dir, name),
            ] + flag_args, port, ws, storaged_env)

        graph_port, graph_ws = _free_port(), _free_port()
        self.graph_addr = f"127.0.0.1:{graph_port}"
        self._register("graphd", [
            sys.executable, "-m", "nebula_tpu.daemons.graphd",
            "--local_ip", "127.0.0.1", "--port", str(graph_port),
            "--ws_http_port", str(graph_ws),
            "--meta_server_addrs", self.meta_addr,
        ] + flag_args, graph_port, graph_ws, env)

        if start:
            self.start()

    def _register(self, name: str, argv: List[str], port: int,
                  ws_port: int, env: Dict[str, str]) -> None:
        self.daemons[name] = ProcDaemon(
            name, argv, port, ws_port,
            os.path.join(self.run_dir, f"{name}.log"), env)

    # ---------------------------------------------------------- boot
    def start(self) -> None:
        """metad first (storaged registration needs it), then storaged,
        then graphd — each gated on its /healthz going green."""
        self.daemons["metad"].spawn()
        self.wait_healthy("metad", self.BOOT_TIMEOUT_S)
        for name in self.storage_names:
            self.daemons[name].spawn()
        for name in self.storage_names:
            self.wait_healthy(name, self.BOOT_TIMEOUT_S)
        self.daemons["graphd"].spawn()
        self.wait_healthy("graphd", self.BOOT_TIMEOUT_S)

    # ------------------------------------------------------ primitives
    def kill(self, name: str, sig: int = signal.SIGKILL) -> None:
        self.daemons[name].kill(sig)

    def restart(self, name: str, wait: bool = True,
                timeout_s: Optional[float] = None) -> None:
        d = self.daemons[name]
        if d.alive():
            d.kill(signal.SIGTERM)
        d.spawn()
        if wait:
            self.wait_healthy(name, timeout_s or self.BOOT_TIMEOUT_S)

    def wait_healthy(self, name: str, timeout_s: float = 30.0) -> dict:
        """Poll the daemon's /healthz until every check passes — the
        PR 5 readiness probe IS the recovery gate.  Raises with the
        daemon's log tail when it never converges (or died)."""
        d = self.daemons[name]
        deadline = time.monotonic() + timeout_s
        last = None
        while time.monotonic() < deadline:
            if not d.alive():
                raise RuntimeError(
                    f"{name} exited (rc={d.proc.returncode}) while "
                    f"waiting for /healthz:\n{d.tail_log()}")
            last = d.healthz()
            if last is not None and last.get("healthy"):
                return last
            time.sleep(0.2)
        raise TimeoutError(
            f"{name} /healthz never went green in {timeout_s}s "
            f"(last: {last}):\n{d.tail_log()}")

    def wait_down(self, name: str, timeout_s: float = 10.0) -> None:
        d = self.daemons[name]
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if not d.alive():
                return
            time.sleep(0.05)
        raise TimeoutError(f"{name} still alive after {timeout_s}s")

    def metrics(self, name: str) -> str:
        return self.daemons[name].metrics()

    def events(self, name: str) -> List[dict]:
        return self.daemons[name].events()

    # ------------------------------------------- directional partitions
    # Link-death chaos over the same harness (the kill matrix's sixth
    # primitive, docs/fault_injection.md "Network partitions"): a
    # partition is ASYMMETRIC — partition(a, b) cuts only a's OUTBOUND
    # calls to b (installed into a's fault injector via its /faults
    # endpoint), so gray failures like "the leader can send heartbeats
    # but not receive acks" are expressible.  Cuts cover every RPC the
    # daemons exchange (storage, device serving, raft replication,
    # meta heartbeats) because they all dial through the one
    # ClientManager seam; the /healthz-and-/metrics ops plane stays
    # reachable — the observer must survive the chaos it causes.
    def _faults_op(self, name: str, body: dict) -> None:
        d = self.daemons[name]
        req = urllib.request.Request(
            f"http://127.0.0.1:{d.ws_port}/faults",
            data=json.dumps(body).encode(), method="PUT",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5) as resp:
            resp.read()

    def partition(self, src: str, dst: str, method: str = "*") -> None:
        """Cut ``src``'s outbound link to ``dst`` (daemon names).
        Direction matters: graphd still reaches a storaged whose
        OUTBOUND rules blackhole it.  Journals net.partitioned inside
        ``src`` so the chaos timeline reads off its /events."""
        target = f"127.0.0.1:{self.daemons[dst].port}"
        self._faults_op(src, {"partition": {"host": target,
                                            "method": method}})

    def heal(self, src: Optional[str] = None,
             dst: Optional[str] = None) -> None:
        """Remove partition cuts: all of them (no args), every cut a
        single daemon installed (``src``), or one directed link
        (``src`` + ``dst``)."""
        names = [src] if src is not None else list(self.daemons)
        host = (f"127.0.0.1:{self.daemons[dst].port}"
                if dst is not None else "*")
        for name in names:
            if self.daemons[name].alive():
                self._faults_op(name, {"heal": {"host": host}})

    def netsplit(self, *groups: List[str]) -> None:
        """Full split: daemons in DIFFERENT groups cannot reach each
        other in either direction (both directed cuts installed);
        daemons within a group stay connected.  Daemons in no group
        (e.g. metad left out) keep full connectivity — the common
        "data plane splits, control plane survives" topology."""
        for g in groups:
            for other in groups:
                if other is g:
                    continue
                for a in g:
                    for b in other:
                        self.partition(a, b)

    def add_graphd(self, name: str,
                   extra_flags: Optional[Dict[str, object]] = None,
                   start: bool = True) -> str:
        """Spawn an EXTRA stateless graphd against the same metad /
        storaged fleet — e.g. a ``storage_backend=cpu`` front end as
        the parity oracle beside a device-serving one (the
        write-while-serve soak reads the same store through both and
        diffs the rows).  Per-instance ``extra_flags`` append AFTER the
        cluster's shared flag set, so later values win.  Returns the
        new graphd's host:port (pass it to ``client(addr=...)``)."""
        port, ws = _free_port(), _free_port()
        flag_args: List[str] = []
        for k, v in (extra_flags or {}).items():
            flag_args += ["--flag", f"{k}={v}"]
        self._register(name, [
            sys.executable, "-m", "nebula_tpu.daemons.graphd",
            "--local_ip", "127.0.0.1", "--port", str(port),
            "--ws_http_port", str(ws),
            "--meta_server_addrs", self.meta_addr,
        ] + self._flag_args + flag_args, port, ws, self._env)
        if start:
            self.daemons[name].spawn()
            self.wait_healthy(name, self.BOOT_TIMEOUT_S)
        return f"127.0.0.1:{port}"

    # ------------------------------------------------------- clients
    def client(self, connect_timeout_s: float = 30.0,
               addr: Optional[str] = None):
        """A GraphClient dialing a graphd over real TCP (fresh
        ClientManager per client: its socket pools must not outlive a
        killed daemon's listener silently).  ``addr`` selects an extra
        front end registered via add_graphd; default is the primary."""
        from ..clients.graph_client import GraphClient
        from ..interface.common import HostAddr
        from ..interface.rpc import ClientManager
        cl = GraphClient(HostAddr.parse(addr or self.graph_addr),
                         client_manager=ClientManager())
        deadline = time.monotonic() + connect_timeout_s
        while True:
            st = cl.connect()
            if st.ok():
                return cl
            if time.monotonic() >= deadline:
                raise RuntimeError(f"graphd connect failed: {st}")
            time.sleep(0.3)

    def round_robin_client(self, addrs: List[str],
                           connect_timeout_s: float = 30.0
                           ) -> "RoundRobinClient":
        """A round-robin balancer façade over one FRESH client per
        graphd address (the horizontal-scale bench's load-balancer
        stand-in — each worker thread should hold its own instance,
        exactly like plain ``client()``)."""
        return RoundRobinClient(
            [self.client(connect_timeout_s=connect_timeout_s,
                         addr=a) for a in addrs])

    # ------------------------------------------------------- teardown
    def stop(self) -> None:
        # every graphd (the primary plus any add_graphd extras) first,
        # then storage, then meta
        graphds = [n for n in self.daemons
                   if n not in self.storage_names and n != "metad"]
        for name in (*graphds, *reversed(self.storage_names), "metad"):
            d = self.daemons.get(name)
            if d is not None and d.alive():
                d.kill(signal.SIGTERM)

    def __enter__(self) -> "ProcCluster":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
