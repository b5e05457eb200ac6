"""Multi-process front-door sharding: SO_REUSEPORT worker pool.

The reference's front door scales inside ONE BEAM node — esockd
acceptor pools fan accepted sockets over scheduler threads that own
every core (src/emqx_listeners.erl:43-81, src/emqx_channel.erl one
process per connection). CPython's GIL forces the process boundary
instead, so the TPU build shards the LISTENER:

- N worker processes each run a full Node (own event loop, own
  ingress batcher, own device plane) and bind the SAME MQTT port with
  ``SO_REUSEPORT`` — the kernel load-balances accepted connections
  across the workers. ONE PROCESS PER CHIP: a chip belongs to one
  process at a time, so on a one-chip host only one worker could own
  the device; there a pool runs on the CPU backend
  (``platform="cpu"`` / ``EMQX_TPU_WORKER_PLATFORM=cpu``, pool-wide)
  and matches on the host. Every worker touches its backend before
  it builds anything, so one that cannot get the device dies at once
  with the reason on stderr instead of silently host-matching behind
  its breaker from its first device batch on;
- the workers join one broker cluster over the socket transport
  (:mod:`emqx_tpu.cluster_net`), so the existing route replication,
  cross-node forwarding, shared-group routing, clientid locking, and
  takeover protocols make the shard split invisible: a subscriber
  accepted by worker 2 receives publishes ingested by worker 0
  through the cluster data plane, exactly like any two cluster nodes;
- worker 0 is the cluster seed; later workers join through its
  transport address (handed over the spawn pipe).

This is the deployment shape for many-core hosts; on a single core
the workers time-share and one process is the better configuration
(``workers=1`` is exactly the plain Node).

Used as a library (:class:`WorkerPool`) and as the ``--workers N``
flag of ``python -m emqx_tpu``.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

_WORKER_MAIN = r"""
import asyncio, os, signal, sys

import jax

if os.environ.get("EMQX_TPU_WORKER_PLATFORM"):
    jax.config.update("jax_platforms",
                      os.environ["EMQX_TPU_WORKER_PLATFORM"])
try:
    # claim the backend NOW: a chip another process holds must fail
    # this worker at start-up, loudly, not at its first device batch
    _d0 = jax.devices()[0]
except Exception as e:
    print(f"worker {sys.argv[1]}: cannot get a device "
          f"({type(e).__name__}: {e}); a chip belongs to one process "
          f"— start the other workers with "
          f"EMQX_TPU_WORKER_PLATFORM=cpu", file=sys.stderr, flush=True)
    sys.exit(3)
print(f"worker {sys.argv[1]}: backend {_d0.platform} "
      f"({_d0.device_kind})", file=sys.stderr, flush=True)

from emqx_tpu.cluster import Cluster
from emqx_tpu.cluster_net import SocketTransport
from emqx_tpu.node import Node


async def main():
    idx = int(sys.argv[1])
    port = int(sys.argv[2])
    host = sys.argv[3]
    seed = sys.argv[4]          # "" for worker 0, else "host:port"
    cookie = sys.argv[5]
    name = f"worker{idx}@{os.getpid()}"
    n = Node(name=name, boot_listeners=False)
    # retained replays are served on every worker
    from emqx_tpu.modules.retainer import RetainerModule
    n.modules.load(RetainerModule)
    tr = SocketTransport(name, cookie=cookie)
    tr.serve()
    cl = Cluster(n, transport=tr)
    lst = n.add_listener(host=host, port=port, reuse_port=True)
    await n.start()
    if seed:
        sh, sp = seed.rsplit(":", 1)
        cl.join_remote(sh, int(sp))
    # READY <listener-port> <transport-port>
    print(f"READY {lst.port} {tr.port}", flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)
    reader = asyncio.StreamReader()
    await loop.connect_read_pipe(
        lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)

    async def stdin_cmds():
        while True:
            line = await reader.readline()
            if not line:
                stop.set()
                return
            parts = line.decode().split()
            if not parts:
                continue
            if parts[0] == "STATS?":
                print(f"STATS {n.cm.connection_count()} "
                      f"{n.metrics.val('messages.delivered')}",
                      flush=True)
            elif parts[0] == "QUIT":
                stop.set()
                return

    cmds = asyncio.create_task(stdin_cmds())
    await stop.wait()
    cmds.cancel()
    cl.leave()
    await n.stop()
    tr.close()


asyncio.run(main())
"""


class WorkerPool:
    """Spawn + supervise N SO_REUSEPORT listener workers."""

    def __init__(self, n_workers: int, port: int = 1883,
                 host: str = "127.0.0.1", cookie: str = "emqx-workers",
                 platform: Optional[str] = None) -> None:
        self.n_workers = n_workers
        self.port = port
        self.host = host
        self.cookie = cookie
        self.platform = platform
        self.procs: List[subprocess.Popen] = []
        self.tports: List[Optional[int]] = []  # per-worker transport
        self._seed_addr = ""

    def _spawn_one(self, idx: int,
                   seed: Optional[str] = None) -> subprocess.Popen:
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        if self.platform:
            env["EMQX_TPU_WORKER_PLATFORM"] = self.platform
        return subprocess.Popen(
            [sys.executable, "-c", _WORKER_MAIN, str(idx),
             str(self.port), self.host,
             self._seed_addr if seed is None else seed, self.cookie],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)

    def _await_ready(self, proc: subprocess.Popen,
                     timeout: float = 120.0):
        import select

        deadline = time.monotonic() + timeout
        buf = b""
        while time.monotonic() < deadline:
            # readline() would block forever on a wedged worker (the
            # known hung-device-init mode); select enforces the budget
            r, _, _ = select.select([proc.stdout],
                                    [], [], min(1.0, deadline
                                                - time.monotonic()))
            if not r:
                continue
            chunk = os.read(proc.stdout.fileno(), 4096)
            if not chunk:
                raise RuntimeError("worker died before READY")
            buf += chunk
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                text = line.decode().strip()
                if text.startswith("READY"):
                    _, lport, tport = text.split()
                    return int(lport), int(tport)
        raise TimeoutError("worker did not become ready")

    def start(self) -> int:
        """Spawn all workers; returns the (shared) listener port.
        A worker failing to come up tears the whole pool down — no
        orphan may keep holding the SO_REUSEPORT port."""
        try:
            p0 = self._spawn_one(0)
            self.procs.append(p0)
            lport, tport = self._await_ready(p0)
            self.tports.append(tport)
            self.port = lport
            self._seed_addr = f"{self.host}:{tport}"
            for i in range(1, self.n_workers):
                p = self._spawn_one(i)
                self.procs.append(p)
                _, tp = self._await_ready(p)
                self.tports.append(tp)
        except BaseException:
            self.stop()
            raise
        return self.port

    #: bound on waiting for a worker process to fully exit before its
    #: slot is reused (restart) or stop() returns. A worker that
    #: hasn't exited still holds its SO_REUSEPORT share of the
    #: listener port: the kernel keeps steering a fraction of new
    #: connections at the dying process, so respawning next to an
    #: orphan silently splits the listener.
    REAP_TIMEOUT = 15.0

    def _reap(self, p: subprocess.Popen) -> None:
        """Ensure ``p`` has exited — TERM, then KILL, each with half
        the budget — raising a clear error if the orphan survives
        (its exit is what releases the SO_REUSEPORT port share)."""
        if p.poll() is not None:
            return
        try:
            p.terminate()
        except OSError:
            pass
        try:
            p.wait(timeout=self.REAP_TIMEOUT / 2)
            return
        except subprocess.TimeoutExpired:
            pass
        try:
            p.kill()
        except OSError:
            pass
        try:
            p.wait(timeout=self.REAP_TIMEOUT / 2)
        except subprocess.TimeoutExpired:
            raise RuntimeError(
                f"worker pid {p.pid} did not exit within "
                f"{self.REAP_TIMEOUT:.0f}s of SIGKILL; the orphan "
                f"still holds its SO_REUSEPORT share of port "
                f"{self.port} — refusing to respawn into a split "
                f"listener") from None

    def restart_worker(self, idx: int) -> None:
        """Respawn a dead worker in place (the reference supervisor's
        restart role). The predecessor is reaped FIRST — a respawn
        next to a live orphan would split the SO_REUSEPORT listener
        between old and new processes. The replacement joins the
        cluster through any LIVE worker's transport — membership is a
        mesh, so losing the original seed (worker 0) doesn't strand
        the pool."""
        self._reap(self.procs[idx])
        seed = ""
        for j, p in enumerate(self.procs):
            if j != idx and p.poll() is None and self.tports[j]:
                seed = f"{self.host}:{self.tports[j]}"
                break
        # the predecessor's transport port is dead the moment we
        # respawn: invalidate BEFORE awaiting readiness so a wedged
        # replacement can't leave a stale port for later restarts
        self.tports[idx] = None
        p = self._spawn_one(idx, seed=seed)
        self.procs[idx] = p
        _, tp = self._await_ready(p)
        self.tports[idx] = tp
        if idx == 0:
            self._seed_addr = f"{self.host}:{tp}"

    def stats(self) -> List[tuple]:
        """[(connections, delivered)] per worker."""
        out = []
        for p in self.procs:
            if p.poll() is not None:
                out.append((0, 0))
                continue
            p.stdin.write(b"STATS?\n")
            p.stdin.flush()
            while True:
                line = p.stdout.readline()
                if not line:
                    out.append((0, 0))
                    break
                text = line.decode().strip()
                if text.startswith("STATS"):
                    _, conns, deliv = text.split()
                    out.append((int(conns), int(deliv)))
                    break
        return out

    def stop(self, timeout: float = 20.0) -> None:
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.stdin.write(b"QUIT\n")
                    p.stdin.flush()
                except Exception:
                    p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + timeout
        stuck = []
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                # a kill without a wait can leave an exiting orphan
                # holding its SO_REUSEPORT port share past stop() —
                # the next pool on this port would share accepts with
                # it. Bounded, with a clear error for the true wedge
                try:
                    p.wait(timeout=self.REAP_TIMEOUT)
                except subprocess.TimeoutExpired:
                    stuck.append(p.pid)
        self.procs.clear()
        # keep bookkeeping aligned for a retried start(): stale
        # tports would otherwise misalign with the new procs list
        self.tports.clear()
        self._seed_addr = ""
        if stuck:
            raise RuntimeError(
                f"worker pids {stuck} survived SIGKILL for "
                f"{self.REAP_TIMEOUT:.0f}s; orphans may still hold "
                f"their SO_REUSEPORT share of port {self.port}")

    def __enter__(self) -> "WorkerPool":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
