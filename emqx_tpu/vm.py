"""Host/runtime introspection — the ``emqx_vm`` analogue.

The reference inspects the BEAM (schedulers, process/port counts,
memory allocators — src/emqx_vm.erl, 487 LoC) to feed ``emqx_ctl``'s
``vm`` command and the $SYS stats. The runtime here is a CPython
host process driving a TPU, so the equivalents are: host memory/CPU,
thread and fd counts, asyncio task count, GC generation counters, and
the JAX device inventory with per-device memory stats where the
backend exposes them.

Everything reads from /proc (Linux) or the stdlib — no psutil in the
image.
"""

from __future__ import annotations

import gc
import os
import resource
import sys
import threading
from typing import Dict, List


def get_memory() -> Dict[str, int]:
    """RSS/VM sizes in bytes (emqx_vm:get_memory/0)."""
    out = {"rss": 0, "vms": 0, "max_rss": 0}
    try:
        with open("/proc/self/statm") as f:
            vms_pages, rss_pages = f.read().split()[:2]
        page = os.sysconf("SC_PAGE_SIZE")
        out["vms"] = int(vms_pages) * page
        out["rss"] = int(rss_pages) * page
    except OSError:
        pass
    # ru_maxrss is KiB on Linux
    out["max_rss"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return out


def raise_fd_limit() -> Dict[str, int]:
    """Raise the process's soft descriptor limit to its hard one —
    what EMQX's tuning guide has operators do with ``ulimit -n``
    before a broker is to hold thousands of sockets (a connection is
    a descriptor; most shells start a process at 1024). Returns
    ``{"soft", "hard", "was"}`` (-1 = unlimited). A hard limit the
    kernel refuses as a soft one leaves it where it was: the caller
    says so and serves on."""
    was, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    soft = was
    if was != hard:
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
            soft = hard
        except (OSError, ValueError):
            pass
    return {"soft": soft, "hard": hard, "was": was}


def get_process_info() -> Dict[str, int]:
    """Thread/fd/task counts — the process-count analogue
    (emqx_vm:get_process_count/0, get_port_count)."""
    try:
        fds = len(os.listdir("/proc/self/fd"))
    except OSError:
        fds = -1
    tasks = 0
    try:
        import asyncio
        tasks = len(asyncio.all_tasks())
    except RuntimeError:
        pass
    return {
        "threads": threading.active_count(),
        "fds": fds,
        "async_tasks": tasks,
        "gc_objects": len(gc.get_objects()),
    }


def get_gc_info() -> Dict[str, int]:
    """Collector generation counters (the BEAM GC stats analogue)."""
    stats = gc.get_stats()
    return {
        f"gen{i}_collections": s.get("collections", 0)
        for i, s in enumerate(stats)
    } | {
        f"gen{i}_collected": s.get("collected", 0)
        for i, s in enumerate(stats)
    }


def loads() -> List[float]:
    """1/5/15-minute load averages (emqx_vm:loads/0)."""
    try:
        return [round(x, 2) for x in os.getloadavg()]
    except OSError:
        return [0.0, 0.0, 0.0]


def cpu_count() -> int:
    """Scheduler-count analogue."""
    return os.cpu_count() or 1


def get_device_info() -> List[Dict[str, object]]:
    """JAX device inventory + memory stats where the PJRT backend
    exposes them (the 'port'/NIF layer of this runtime)."""
    out: List[Dict[str, object]] = []
    try:
        import jax
        for d in jax.devices():
            info: Dict[str, object] = {
                "id": d.id, "platform": d.platform,
                "kind": getattr(d, "device_kind", "?"),
            }
            try:
                ms = d.memory_stats()
                if ms:
                    info["bytes_in_use"] = ms.get("bytes_in_use")
                    info["bytes_limit"] = ms.get("bytes_limit")
            except Exception:
                pass
            out.append(info)
    except Exception:
        pass
    return out


def get_system_info() -> Dict[str, object]:
    """The full ``ctl vm`` payload (emqx_vm:get_system_info/0)."""
    return {
        "python": sys.version.split()[0],
        "cpu_count": cpu_count(),
        "load": loads(),
        "memory": get_memory(),
        "process": get_process_info(),
        "gc": get_gc_info(),
        "devices": get_device_info(),
    }
