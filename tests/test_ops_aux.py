"""Host monitors, PSK lookup, GC policies, logger metadata
(emqx_os_mon / emqx_vm_mon / emqx_sys_mon / emqx_psk / emqx_gc /
emqx_logger parity)."""

import logging

import pytest

from emqx_tpu import logger as elog
from emqx_tpu.alarm import AlarmManager
from emqx_tpu.gc import GcPolicy, GlobalGc
from emqx_tpu.hooks import Hooks
from emqx_tpu.monitors import (OsMon, SysMon, VmMon, read_cpu_times,
                               read_mem_usage)
from emqx_tpu.psk import PskAuth


# -- os_mon -----------------------------------------------------------------

def test_os_mon_cpu_watermarks():
    alarms = AlarmManager()
    mon = OsMon(alarms, cpu_high=0.8, cpu_low=0.6)
    mon.check(0.9, None)
    assert any(a.name == "high_cpu_usage"
               for a in alarms.get_alarms("activated"))
    mon.check(0.7, None)  # between: hysteresis, stays active
    assert any(a.name == "high_cpu_usage"
               for a in alarms.get_alarms("activated"))
    mon.check(0.5, None)
    assert not alarms.get_alarms("activated")


def test_os_mon_mem_watermarks():
    alarms = AlarmManager()
    mon = OsMon(alarms, mem_high=0.8, mem_low=0.6)
    mon.check(None, 0.95)
    assert any(a.name == "high_memory_usage"
               for a in alarms.get_alarms("activated"))
    mon.check(None, 0.3)
    assert not alarms.get_alarms("activated")


def test_os_mon_proc_readers():
    # live /proc readings on Linux: sane ranges
    cpu = read_cpu_times()
    assert cpu is None or (cpu[1] >= cpu[0] >= 0)
    mem = read_mem_usage()
    assert mem is None or 0.0 <= mem <= 1.0
    # a second CPU sample yields a usage fraction
    mon = OsMon(AlarmManager())
    mon.sample_cpu()
    u = mon.sample_cpu()
    assert u is None or 0.0 <= u <= 1.0


# -- vm_mon -----------------------------------------------------------------

def test_vm_mon_count_watermark():
    alarms = AlarmManager()
    mon = VmMon(alarms, count_fn=lambda: 0, max_count=100,
                high=0.8, low=0.6)
    mon.check(90)
    assert any(a.name == "too_many_processes"
               for a in alarms.get_alarms("activated"))
    mon.check(50)
    assert not alarms.get_alarms("activated")


# -- sys_mon ----------------------------------------------------------------

def test_sys_mon_long_schedule_and_gc():
    hooks = Hooks()
    events = []
    hooks.add("sysmon.long_schedule", lambda ms: events.append(ms))
    mon = SysMon(hooks=hooks, long_schedule_ms=100.0)
    mon.check_lag(1.0, 1.05)   # 50ms lag: fine
    assert mon.long_schedule_count == 0
    mon.check_lag(1.0, 1.5)    # 500ms lag
    assert mon.long_schedule_count == 1 and events == [500.0]
    mon.on_long_gc(150.0)
    assert mon.long_gc_count == 1


def test_sys_mon_gc_hook_install_remove():
    import gc
    mon = SysMon()
    mon.install_gc_hook()
    assert mon._on_gc in gc.callbacks
    gc.collect()  # must not raise through the callback
    mon.remove_gc_hook()
    assert mon._on_gc not in gc.callbacks


# -- psk --------------------------------------------------------------------

def test_psk_lookup_and_chain():
    hooks = Hooks()
    auth = PskAuth(hooks, {"dev1": b"secret1"})
    assert auth.lookup("dev1") == b"secret1"
    assert auth.lookup("ghost") is None
    auth.add("dev2", b"k2")
    assert auth.lookup("dev2") == b"k2"
    auth.remove("dev2")
    assert auth.lookup("dev2") is None
    # a second resolver fills misses; the first keeps priority
    PskAuth(hooks, {"dev1": b"shadowed", "dev3": b"k3"})
    assert auth.lookup("dev1") == b"secret1"
    assert auth.lookup("dev3") == b"k3"


# -- gc ---------------------------------------------------------------------

def test_gc_policy_triggers():
    p = GcPolicy(count=10, bytes_=1000)
    for _ in range(9):
        assert not p.inc(1, 10)
    assert p.inc(1, 10)          # count trigger
    assert p.collections == 1
    assert p.inc(1, 2000)        # bytes trigger
    assert p.collections == 2


def test_global_gc_runs():
    g = GlobalGc(interval=None)
    freed = g.run_gc()
    assert g.runs == 1 and freed >= 0


@pytest.mark.parametrize("routes, frozen", [
    (0, False), (99_999, False), (100_000, True), (4_000_000, True)])
def test_freeze_resident_only_where_the_tables_are_large(routes, frozen):
    import gc

    from emqx_tpu.gc import freeze_resident

    before, thresholds = gc.get_freeze_count(), gc.get_threshold()
    try:
        assert freeze_resident(routes) is frozen
        assert (gc.get_freeze_count() > before) is frozen
        # a frozen heap switches the collector's quarter rule off:
        # full collections are held to every hundredth gen-1 one
        assert gc.get_threshold() == (
            thresholds[:2] + (100,) if frozen else thresholds)
    finally:
        gc.unfreeze()  # a test process keeps its collector
        gc.set_threshold(*thresholds)


def test_node_start_freezes_what_it_restored_at_boot(monkeypatch):
    """A node that starts to serve with its subscription tables in
    place moves them out of the collector's reach; a small node (every
    other test's) does not."""
    import asyncio
    import gc

    from emqx_tpu import gc as egc
    from emqx_tpu.node import Node

    class Sink:
        def deliver(self, topic_filter, msg):
            pass

    async def started(n_filters):
        node = Node(boot_listeners=False)
        sink = Sink()
        for i in range(n_filters):
            node.broker.subscribe(sink, f"t/{i}/+")
        before, thresholds = gc.get_freeze_count(), gc.get_threshold()
        await node.start()
        try:
            return gc.get_freeze_count() - before
        finally:
            await node.stop()
            gc.unfreeze()
            gc.set_threshold(*thresholds)

    monkeypatch.setattr(egc, "FREEZE_MIN_ROUTES", 50)
    assert asyncio.run(started(10)) == 0
    assert asyncio.run(started(50)) > 0


# -- logger -----------------------------------------------------------------

def test_logger_metadata_and_formatter():
    elog.clear_metadata()
    elog.set_metadata_clientid("c1")
    elog.set_metadata_peername(("10.0.0.1", 4321))
    assert elog.get_metadata() == {"clientid": "c1",
                                   "peername": "10.0.0.1:4321"}
    rec = logging.LogRecord("emqx_tpu.x", logging.INFO, "f", 1,
                            "hello %s", ("world",), None)
    assert elog.MetadataFilter().filter(rec)
    line = elog.BrokerFormatter().format(rec)
    assert "c1@10.0.0.1:4321 hello world" in line
    elog.clear_metadata()
    rec2 = logging.LogRecord("emqx_tpu.x", logging.INFO, "f", 1,
                             "plain", (), None)
    elog.MetadataFilter().filter(rec2)
    line2 = elog.BrokerFormatter().format(rec2)
    assert line2.endswith("plain") and "@" not in line2


def test_logger_setup_attaches_handler():
    sink = []

    class ListHandler(logging.Handler):
        def emit(self, record):
            sink.append(self.format(record))

    h = elog.setup(level=logging.DEBUG, handler=ListHandler())
    try:
        elog.set_metadata_clientid("cX")
        logging.getLogger("emqx_tpu.test").info("msg")
        assert any("cX" in line and "msg" in line for line in sink)
    finally:
        logging.getLogger("emqx_tpu").removeHandler(h)
        elog.clear_metadata()


def test_vm_introspection():
    from emqx_tpu import vm
    info = vm.get_system_info()
    assert info["cpu_count"] >= 1
    assert info["memory"]["rss"] > 0
    assert info["process"]["threads"] >= 1
    assert len(info["load"]) == 3
    assert isinstance(info["devices"], list)


def test_ctl_vm_command():
    from emqx_tpu.node import Node
    n = Node(boot_listeners=False)
    out = n.ctl.run(["vm"])
    assert '"cpu_count"' in out and '"rss"' in out


# -- profiling (SURVEY §5 tracing/profiling: jax-profiler) --

def test_profiler_trace_writes_artifacts(tmp_path):
    import jax
    import jax.numpy as jnp

    from emqx_tpu.profiling import trace

    logdir = str(tmp_path / "trace")
    with trace(logdir):
        jax.block_until_ready(jnp.ones((32, 32)) @ jnp.ones((32, 32)))
    import os
    found = [os.path.join(dp, f) for dp, _, fs in os.walk(logdir)
             for f in fs]
    assert found, "profiler wrote no trace artifacts"


def test_inline_rebuild_recorded_in_rebuild_stage():
    from emqx_tpu.router import MatcherConfig, Router
    from emqx_tpu.telemetry import Telemetry

    r = Router(MatcherConfig(device_min_filters=0))
    r.telemetry = tel = Telemetry()
    r.add_route("prof/+")
    r.match_filters(["prof/x"])
    assert tel.hists["rebuild"].count >= 1
    assert tel.rebuilding == 0 and tel.rebuild_end > 0.0
