"""Host resource monitors → alarms.

The reference watches the BEAM and the OS and raises alarms on
watermarks: ``emqx_os_mon`` (CPU/memory, src/emqx_os_mon.erl),
``emqx_vm_mon`` (process count, src/emqx_vm_mon.erl) and
``emqx_sys_mon`` (long_gc / long_schedule / busy_port VM events,
src/emqx_sys_mon.erl). Here the host runtime is a Python process on
Linux, so:

  - :class:`OsMon` reads ``/proc/stat`` deltas and ``/proc/meminfo``;
  - :class:`VmMon` watches a supplied count (connections by default —
    the asyncio analogue of the process count) against a watermark;
  - :class:`SysMon` measures event-loop lag with a heartbeat on the
    loop (the analogue of long_schedule: the scheduler not getting
    to our task on time), records each stall with the stack a
    watcher thread took of it, and times every Python collection via
    ``gc.callbacks`` (the analogue of long_gc).

Each monitor has a pure ``check(...)`` (unit-testable with injected
readings) and an async ``run()`` loop the node supervises. Alarm
names mirror the reference: ``high_cpu_usage``, ``high_memory_usage``,
``too_many_processes``.
"""

from __future__ import annotations

import asyncio
import gc as _gc
import logging
import sys
import threading
import time
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation

from emqx_tpu.alarm import AlarmManager
from emqx_tpu.concurrency import bg_thread
from emqx_tpu.metrics import (I_SELECT_CLIENTS_NS, I_SELECT_DEVICE_NS,
                              I_SELECT_POLL_NS, I_WALL_NS)
from emqx_tpu.telemetry import STALL_S
from emqx_tpu.tracing import frame_stack

log = logging.getLogger("emqx_tpu.monitors")


def read_cpu_times() -> Optional[tuple]:
    """(busy, total) jiffies from /proc/stat, None off-Linux."""
    try:
        with open("/proc/stat") as f:
            parts = f.readline().split()
        vals = [int(v) for v in parts[1:9]]
        idle = vals[3] + vals[4]  # idle + iowait
        total = sum(vals)
        return total - idle, total
    except (OSError, ValueError, IndexError):
        return None


def read_mem_usage() -> Optional[float]:
    """Used-memory fraction from /proc/meminfo, None off-Linux."""
    try:
        info = {}
        with open("/proc/meminfo") as f:
            for line in f:
                k, _, rest = line.partition(":")
                info[k] = int(rest.split()[0])
        total = info["MemTotal"]
        avail = info.get(
            "MemAvailable",
            info.get("MemFree", 0) + info.get("Buffers", 0)
            + info.get("Cached", 0))
        return (total - avail) / total if total else None
    except (OSError, ValueError, KeyError):
        return None


class OsMon:
    """CPU/memory watermark monitor (emqx_os_mon defaults:
    cpu_high_watermark 80%, cpu_low_watermark 60%, 60s interval;
    mem watermarks from os_mon's memsup)."""

    def __init__(self, alarms: AlarmManager,
                 cpu_high: float = 0.80, cpu_low: float = 0.60,
                 mem_high: float = 0.80, mem_low: float = 0.60,
                 interval: float = 60.0) -> None:
        self.alarms = alarms
        self.cpu_high = cpu_high
        self.cpu_low = cpu_low
        self.mem_high = mem_high
        self.mem_low = mem_low
        self.interval = interval
        self._prev_cpu: Optional[tuple] = None

    def check(self, cpu_usage: Optional[float],
              mem_usage: Optional[float]) -> None:
        """Apply one reading pair (fractions in [0,1] or None)."""
        if cpu_usage is not None:
            if cpu_usage > self.cpu_high:
                self.alarms.activate(
                    "high_cpu_usage", {"usage": round(cpu_usage, 4)},
                    f"cpu usage {cpu_usage:.0%} > {self.cpu_high:.0%}")
            elif cpu_usage < self.cpu_low:
                self.alarms.deactivate("high_cpu_usage")
        if mem_usage is not None:
            if mem_usage > self.mem_high:
                self.alarms.activate(
                    "high_memory_usage", {"usage": round(mem_usage, 4)},
                    f"mem usage {mem_usage:.0%} > {self.mem_high:.0%}")
            elif mem_usage < self.mem_low:
                self.alarms.deactivate("high_memory_usage")

    def sample_cpu(self) -> Optional[float]:
        cur = read_cpu_times()
        if cur is None:
            return None
        usage = None
        if self._prev_cpu is not None:
            busy = cur[0] - self._prev_cpu[0]
            total = cur[1] - self._prev_cpu[1]
            if total > 0:
                usage = busy / total
        self._prev_cpu = cur
        return usage

    async def run(self) -> None:
        while True:
            self.check(self.sample_cpu(), read_mem_usage())
            await asyncio.sleep(self.interval)


class VmMon:
    """Count-watermark monitor (emqx_vm_mon: process_count against
    process_high_watermark of max; here the count defaults to live
    connections against the listener limit)."""

    def __init__(self, alarms: AlarmManager, count_fn: Callable[[], int],
                 max_count: int, high: float = 0.80, low: float = 0.60,
                 interval: float = 30.0,
                 alarm_name: str = "too_many_processes") -> None:
        self.alarms = alarms
        self.count_fn = count_fn
        self.max_count = max_count
        self.high = high
        self.low = low
        self.interval = interval
        self.alarm_name = alarm_name

    def check(self, count: int) -> None:
        if self.max_count <= 0:
            return
        frac = count / self.max_count
        if frac > self.high:
            self.alarms.activate(
                self.alarm_name,
                {"count": count, "max": self.max_count},
                f"{count}/{self.max_count} > {self.high:.0%}")
        elif frac < self.low:
            self.alarms.deactivate(self.alarm_name)

    async def run(self) -> None:
        while True:
            self.check(self.count_fn())
            await asyncio.sleep(self.interval)


class SysMon:
    """Runtime-event monitor: event-loop lag ≈ long_schedule, GC
    pauses ≈ long_gc (emqx_sys_mon publishes these to '$SYS' and
    counts them; we count + log + optionally alarm).

    Lag comes from one heartbeat on the loop: a timer that re-arms
    itself every :attr:`BEAT_S` and reads how late it ran. A beat
    more than ``telemetry.STALL_S`` (50 ms) late is a STALL: while
    telemetry is enabled a watcher thread has by then taken the loop
    thread's stack once (and the other busy threads' innermost
    frames), and the beat — the loop is back — writes one record
    (start, length, frames, whether a collection or an automaton
    rebuild overlapped) into the telemetry's stall ring
    (``ctl telemetry stalls``) and counts ``loop.stalls`` /
    ``loop.stall.ns``. A beat later than ``long_schedule_ms`` is a
    long_schedule event, whose log line names the frame. The gc hook
    feeds ``gc.ns.gen*`` / ``gc.collections.gen*`` for every
    collection and wraps each in an ``emqx/gc`` profiler annotation."""

    #: the heartbeat's period, seconds
    BEAT_S = 0.02
    #: innermost frames kept in a stall record
    STALL_FRAMES = 12

    def __init__(self, metrics=None, hooks=None,
                 long_schedule_ms: float = 240.0,
                 long_gc_ms: float = 100.0,
                 tick: float = 1.0, telemetry=None,
                 ingress=None) -> None:
        self.metrics = metrics
        #: the node's IngressBatcher (None = none): the selector's
        #: shadow reads from it what the loop is waiting for
        self.ingress = ingress
        if metrics is not None:
            metrics.new("sysmon.long_gc")
            metrics.new("sysmon.long_schedule")
        self.hooks = hooks
        self.telemetry = telemetry
        self.long_schedule_ms = long_schedule_ms
        self.long_gc_ms = long_gc_ms
        self.tick = tick
        self.long_schedule_count = 0
        self.long_gc_count = 0
        self._gc_t0: Optional[float] = None
        self._gc_ann = None
        self._gc_installed = False
        # per-loop scheduling lag (ms), index 0 = the main loop.
        # Peer entries are written by their own loop's probe callback
        # and read by the main-loop tick / stats fold — float stores
        # are atomic under the GIL, no lock needed
        self.loop_group = None
        self.loop_lags: List[float] = [0.0]
        self._probe_seq: List[int] = [0]
        self._seen_seq: List[int] = [0]
        # heartbeat state (written on the loop, read by the watcher)
        self._loop = None
        self._loop_tid = 0
        self._beat_h = None
        self._beat_at = 0.0    # when the last beat ran
        self._beat_due = 0.0   # when the next one should
        self._beat_gc_s = 0.0  # telemetry.gc_s at the last beat
        self._lag_max_s = 0.0  # worst lateness since the last tick
        # (due, loop frames, other threads) of the stall the watcher
        # saw last
        self._stall_stack: Optional[tuple] = None
        self._watch_thread: Optional[threading.Thread] = None
        self._watch_stop = threading.Event()
        self._selector = None  # the loop's selector while wrapped

    def bind_loops(self, loop_group) -> None:
        """Extend lag monitoring over every LoopGroup loop: each tick
        posts a timestamped probe to every live peer loop; the probe
        callback (running ON that loop) records its scheduling delay."""
        self.loop_group = loop_group
        n = loop_group.n
        self.loop_lags = [0.0] * n
        self._probe_seq = [0] * n
        self._seen_seq = [0] * n

    def _probe_loop(self, idx: int, t_post: float) -> None:
        # runs on peer loop `idx`: the post → run delay IS the lag
        self.loop_lags[idx] = (time.perf_counter() - t_post) * 1000.0
        self._probe_seq[idx] += 1

    # -- GC pause tracking (gc.callbacks) ------------------------------

    def install_gc_hook(self) -> None:
        if not self._gc_installed:
            _gc.callbacks.append(self._on_gc)
            self._gc_installed = True

    def remove_gc_hook(self) -> None:
        if self._gc_installed:
            try:
                _gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass
            self._gc_installed = False

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            tel = self.telemetry
            if tel is not None and tel.config.enabled:
                ann = TraceAnnotation(
                    "emqx/gc", gen=info.get("generation", 0))
                ann.__enter__()
                self._gc_ann = ann
            self._gc_t0 = time.perf_counter()
        elif phase == "stop" and self._gc_t0 is not None:
            dt = time.perf_counter() - self._gc_t0
            self._gc_t0 = None
            ann = self._gc_ann
            if ann is not None:
                self._gc_ann = None
                ann.__exit__(None, None, None)
                self.telemetry.gc_done(info.get("generation", 0), dt)
            ms = dt * 1000.0
            if ms > self.long_gc_ms:
                self.on_long_gc(ms)

    # -- events --------------------------------------------------------

    def on_long_gc(self, ms: float) -> None:
        self.long_gc_count += 1
        log.warning("long_gc: %.1fms", ms)
        if self.metrics is not None:
            self.metrics.inc("sysmon.long_gc")
        if self.hooks is not None:
            self.hooks.run("sysmon.long_gc", (ms,))

    def on_long_schedule(self, ms: float,
                         frame: Optional[str] = None) -> None:
        self.long_schedule_count += 1
        if frame is not None:
            log.warning("long_schedule: event loop lagged %.1fms "
                        "in %s", ms, frame)
        else:
            log.warning("long_schedule: event loop lagged %.1fms", ms)
        if self.metrics is not None:
            self.metrics.inc("sysmon.long_schedule")
        if self.hooks is not None:
            self.hooks.run("sysmon.long_schedule", (ms,))

    def check_lag(self, expected_s: float, actual_s: float,
                  frame: Optional[str] = None) -> None:
        lag_ms = (actual_s - expected_s) * 1000.0
        if lag_ms > self.long_schedule_ms:
            self.on_long_schedule(lag_ms, frame)

    # -- the heartbeat and the stall watcher ---------------------------

    def start_heartbeat(self, loop) -> None:
        """Arm the heartbeat on ``loop`` (the calling thread's) and,
        while telemetry is enabled, the watcher thread beside it."""
        self._loop = loop
        self._loop_tid = threading.get_ident()
        now = time.perf_counter()
        self._beat_at = now
        self._beat_due = now + self.BEAT_S
        self._beat_h = loop.call_later(self.BEAT_S, self._beat)
        tel = self.telemetry
        if tel is not None and tel.config.enabled \
                and self._watch_thread is None:
            self._watch_stop.clear()
            self._watch_thread = threading.Thread(
                target=self._watch, name="loop-watch", daemon=True)
            self._watch_thread.start()
            self._time_selector(loop, tel.loop_clock())

    def _time_selector(self, loop, lc) -> None:
        """``loop.select.ns``: time the loop spends inside its
        selector, by shadowing the selector's ``select`` on the
        instance (asyncio has no hook for it; a loop without a
        ``_selector`` — not the stock selector loop — goes untimed).

        Each call's time also goes to what the loop was waiting for
        (``loop.select.poll.ns`` / ``.device.ns`` / ``.clients.ns``,
        metrics.LOOP_METRICS), decided from the ingress's state at the
        call's ENTRY: whatever changes that state (a fetch returning,
        a socket speaking, a peer loop's submit) wakes the loop, so
        it holds for the whole call. Attribute loads only: no lock
        and no call in here."""
        sel = getattr(loop, "_selector", None)
        if lc is None or sel is None \
                or "select" in getattr(sel, "__dict__", {"select": 0}):
            return  # untimed; or another node on this loop times it
        select = sel.select
        now = time.perf_counter
        ing = self.ingress

        def timed_select(timeout=None):
            t0 = now()
            n0 = lc.inner
            if timeout == 0:
                kind = I_SELECT_POLL_NS  # ready handles: kernel work
            elif ing is None:
                kind = -1
            elif ing._on_path:
                kind = I_SELECT_DEVICE_NS  # a fetch is still out
            elif ing._inflight or ing._pending:
                kind = -1  # a linger timer, an ordered tail
            else:
                kind = I_SELECT_CLIENTS_NS  # nothing until a socket speaks
            try:
                return select(timeout)
            finally:
                lc.select_leave(kind, t0, n0)

        sel.select = timed_select
        self._selector = sel

    def stop_heartbeat(self) -> None:
        if self._beat_h is not None:
            self._beat_h.cancel()
            self._beat_h = None
        sel = self._selector
        if sel is not None:
            self._selector = None
            try:
                del sel.select  # the instance shadow; the method stays
            except AttributeError:
                pass
        t = self._watch_thread
        if t is not None:
            self._watch_stop.set()
            t.join(2.0)
            self._watch_thread = None

    def _beat(self) -> None:
        now = time.perf_counter()
        due = self._beat_due
        late = now - due
        if late > self._lag_max_s:
            self._lag_max_s = late
        tel = self.telemetry
        if late > STALL_S:
            self._on_stall(due, late)
        if tel is not None:
            self._beat_gc_s = tel.gc_s
            m = tel.metrics
            if m is not None and tel.config.enabled:
                m.add_at(I_WALL_NS, int((now - self._beat_at) * 1e9))
        self._beat_at = now
        self._beat_due = now + self.BEAT_S
        self._beat_h = self._loop.call_later(self.BEAT_S, self._beat)

    def _on_stall(self, due: float, late: float) -> None:
        """The loop is back from a stall that began around ``due``
        and lasted ``late`` seconds."""
        frames: List[str] = []
        others: dict = {}
        seen = self._stall_stack
        if seen is not None and seen[0] == due:
            frames = seen[1][-self.STALL_FRAMES:]
            others = seen[2]
        tel = self.telemetry
        if tel is not None and tel.config.enabled:
            tel.note_stall({
                "ts": time.time() - late,
                "ms": round(late * 1000.0, 3),
                # outermost first; the last one is where the loop was
                "frames": frames,
                # thread name -> innermost frames of the threads that
                # were not parked at that instant
                "others": others,
                "gc_ms": round((tel.gc_s - self._beat_gc_s) * 1000.0,
                               3),
                "rebuild": (tel.rebuilding > 0
                            or tel.rebuild_end > self._beat_at),
            })
        self.check_lag(0.0, late, frames[-1] if frames else None)

    @bg_thread
    def _watch(self) -> None:
        """The watcher: when the beat is overdue past the stall mark,
        take the loop thread's stack ONCE, hold an ``emqx/stall``
        annotation open until the loop beats again."""
        stop = self._watch_stop
        while not stop.wait(self.BEAT_S):
            due = self._beat_due
            if time.perf_counter() - due <= STALL_S:
                continue
            try:
                frames, others = self._stacks()
            except Exception:
                # a torn frame walk must not kill the watcher
                frames, others = [], {}
            self._stall_stack = (due, frames, others)
            with TraceAnnotation("emqx/stall"):
                while self._beat_due == due \
                        and not stop.wait(0.005):
                    pass

    #: innermost frames that mean "this thread is parked": such
    #: threads are left out of a stall record's ``others``
    _PARKED = ("threading.py:wait", "queue.py:get", "thread.py:_worker",
               "selectors.py:select")

    def _stacks(self) -> tuple:
        """The loop thread's stack, and the innermost frames of every
        other thread that is not parked: a loop caught at an ordinary
        line with no collection running was held off the GIL or the
        CPU, and the thread that held it is in this list."""
        me = threading.get_ident()
        names = {t.ident: t.name for t in threading.enumerate()}
        frames: List[str] = []
        others = {}
        current = sys._current_frames()
        try:
            for ident, frame in current.items():
                if ident == self._loop_tid:
                    frames = frame_stack(frame)
                elif ident != me:
                    top = frame_stack(frame, 4)
                    if top and top[-1] not in self._PARKED:
                        others[names.get(ident, str(ident))] = top
        finally:
            del current  # drop the frame references promptly
        return frames, others

    async def run(self) -> None:
        self.install_gc_hook()
        self.start_heartbeat(asyncio.get_running_loop())
        try:
            while True:
                await asyncio.sleep(self.tick)
                # the heartbeat's worst lateness over this tick (its
                # long_schedule events fired as they happened)
                self.loop_lags[0] = self._lag_max_s * 1000.0
                self._lag_max_s = 0.0
                lg = self.loop_group
                if lg is not None:
                    # fold last tick's peer probes (event firing stays
                    # on the main loop — hooks/metrics are not posted
                    # from peer threads), then launch the next round
                    for i in range(1, lg.n):
                        if self._probe_seq[i] != self._seen_seq[i]:
                            self._seen_seq[i] = self._probe_seq[i]
                            lag = self.loop_lags[i]
                            if lag > self.long_schedule_ms:
                                self.on_long_schedule(lag)
                        if lg.alive(i):
                            try:
                                lg.post(i, self._probe_loop, i,
                                        time.perf_counter())
                            except RuntimeError:
                                pass  # loop died since alive()
        finally:
            self.stop_heartbeat()
            self.remove_gc_hook()
