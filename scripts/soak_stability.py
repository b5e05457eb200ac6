"""Long-run stability soak: sustained pub/sub + route churn +
client reconnects against one live node, RSS sampled throughout.

The 3-minute suite can't see slow leaks (retained wire caches,
un-reaped subscriptions, patcher garbage, growing cast buffers);
this drives the full socket path for SOAK_MINUTES and reports the
RSS trend. A healthy broker plateaus after warmup; monotonic growth
per cycle is a leak.

Usage: SOAK_MINUTES=30 python scripts/soak_stability.py
"""

import asyncio
import json
import os
import random
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

# a soak exercises the host stack: CPU unless told otherwise
jax.config.update("jax_platforms",
                  os.environ.get("SOAK_PLATFORM", "cpu"))

from emqx_tpu.mqtt import constants as C  # noqa: E402

MINUTES = float(os.environ.get("SOAK_MINUTES", "30"))
CLIENTS = int(os.environ.get("SOAK_CLIENTS", "40"))
SAMPLE_S = float(os.environ.get("SOAK_SAMPLE_S", "30"))
# >0 pre-loads background wildcard filters so the broker runs the
# DEVICE publish regime (above device_min_filters) during the soak
BG_FILTERS = int(os.environ.get("SOAK_BG_FILTERS", "0"))
# SOAK_RETAIN=1: a retained-churn dimension — clients publish
# retained messages on CHURNING topic names (unique words over time,
# the RetainIndex leak surface: word-intern table, row slots, device
# cache) and wildcard-subscribe so the reverse index actually runs;
# SOAK_RETAIN_THRESHOLD forces the device path (default 64)
RETAIN = os.environ.get("SOAK_RETAIN", "") == "1"
RETAIN_THRESHOLD = int(os.environ.get("SOAK_RETAIN_THRESHOLD", "64"))


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _rss_now_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024.0
    return 0.0


async def _client_loop(idx: int, port: int, stop: asyncio.Event,
                       stats: dict):
    from tests.mqtt_client import TestClient

    rng = random.Random(idx)
    seq = idx * 10_000_000  # unique retained names per client, forever
    while not stop.is_set():
        cli = TestClient(f"soak{idx}", version=C.MQTT_V5)
        try:
            await cli.connect(port=port, timeout=30)
            for _round in range(rng.randint(3, 10)):
                if stop.is_set():
                    break
                if RETAIN:
                    # store a fresh-named retained message, delete an
                    # older one (empty payload), and wildcard-sub so
                    # the reverse index matches on the device path
                    seq += 1
                    await cli.publish(f"ret/{idx}/s{seq}", b"r",
                                      qos=0, retain=True)
                    if seq > 3:
                        await cli.publish(f"ret/{idx}/s{seq - 3}",
                                          b"", qos=0, retain=True)
                    await cli.subscribe(f"ret/{idx}/#", qos=0)
                    await cli.unsubscribe(f"ret/{idx}/#")
                    stats["retains"] = stats.get("retains", 0) + 1
                flt = f"soak/{rng.randrange(200)}/+"
                await cli.subscribe(flt, qos=rng.randrange(2))
                for _ in range(20):
                    await cli.publish(
                        f"soak/{rng.randrange(200)}/x",
                        b"p" * rng.randrange(8, 200),
                        qos=rng.randrange(2), timeout=30)
                    stats["pubs"] += 1
                # drain whatever arrived
                try:
                    while True:
                        await asyncio.wait_for(cli.inbox.get(), 0.01)
                        stats["recvs"] += 1
                except asyncio.TimeoutError:
                    pass
                await cli.unsubscribe(flt)
                stats["churns"] += 1
            await cli.disconnect()
        except Exception as e:
            stats["errors"] += 1
            stats["last_error"] = repr(e)[:120]
        finally:
            try:
                await cli.close()
            except Exception:
                pass
        stats["reconnects"] += 1


async def main():
    from emqx_tpu.node import Node

    n = Node(batch_ingress=True)
    n.add_listener(port=0)
    await n.start()
    if BG_FILTERS:
        for i in range(BG_FILTERS):
            n.router.add_route(f"bg/{i}/+")
        print(json.dumps({"bg_filters": BG_FILTERS,
                          "device_regime":
                          n.router.use_device_now()}), flush=True)
    if RETAIN:
        ret = n.modules._loaded.get("retainer")
        if ret is None:
            from emqx_tpu.modules.retainer import RetainerModule
            ret = n.modules.load(RetainerModule)
        ret.index_device_threshold = RETAIN_THRESHOLD
        print(json.dumps({"retain_dim": True,
                          "index_device_threshold":
                          RETAIN_THRESHOLD}), flush=True)
    port = n.listeners[0].port
    stop = asyncio.Event()
    stats = {"pubs": 0, "recvs": 0, "churns": 0, "reconnects": 0,
             "errors": 0}
    tasks = [asyncio.create_task(_client_loop(i, port, stop, stats))
             for i in range(CLIENTS)]
    samples = []
    t_end = time.monotonic() + MINUTES * 60
    while time.monotonic() < t_end:
        await asyncio.sleep(SAMPLE_S)
        samples.append(round(_rss_now_mb(), 1))
        extra = {}
        if RETAIN:
            ret = n.modules._loaded.get("retainer")
            if ret is not None:
                extra = {"retained": len(ret._store),
                         "index_words": len(ret._index._table)}
        print(json.dumps({"t_min": round(
            (time.monotonic() - (t_end - MINUTES * 60)) / 60, 1),
            "rss_mb": samples[-1], **stats, **extra}), flush=True)
    stop.set()
    await asyncio.gather(*tasks, return_exceptions=True)
    await n.stop()

    # trend over the second half (first half is warmup/jit)
    half = samples[len(samples) // 2:]
    growth = (half[-1] - half[0]) if len(half) >= 2 else 0.0
    print(json.dumps({
        "metric": "stability_soak",
        "minutes": MINUTES, "clients": CLIENTS,
        "rss_start_mb": samples[0] if samples else None,
        "rss_end_mb": samples[-1] if samples else None,
        "rss_secondhalf_growth_mb": round(growth, 1),
        "verdict": ("leak-suspect" if growth > 50 else "stable"),
        **stats,
    }), flush=True)


if __name__ == "__main__":
    asyncio.run(main())
