"""Flap detection → auto-ban (emqx_tpu/flapping.py; reference
src/emqx_flapping.erl): detect/ban thresholds, window reset, gc, and
the flapping→banned interaction under a reconnect-storm shape."""

import time

from emqx_tpu.banned import Banned
from emqx_tpu.flapping import Flapping, FlappingConfig


def _mk(max_count=5, window=60.0, ban_time=300.0, banned=None):
    return Flapping(
        banned=banned if banned is not None else Banned(),
        config=FlappingConfig(max_count=max_count, window=window,
                              ban_time=ban_time))


def test_threshold_bans_client():
    fl = _mk(max_count=3)
    for _ in range(2):
        fl.disconnected("c1")
    assert fl.banned.look_up("clientid", "c1") is None
    fl.disconnected("c1")  # third strike inside the window
    rule = fl.banned.look_up("clientid", "c1")
    assert rule is not None
    assert rule.by == "flapping"
    # the track resets after the ban: counting starts over
    assert "c1" not in fl._tracks


def test_below_threshold_never_bans():
    fl = _mk(max_count=10)
    for _ in range(9):
        fl.disconnected("quiet")
    assert fl.banned.look_up("clientid", "quiet") is None


def test_window_expiry_resets_count():
    fl = _mk(max_count=3, window=60.0)
    fl.disconnected("c2")
    fl.disconnected("c2")
    # age the track past the window: the next disconnect starts a
    # fresh one instead of completing the old streak
    fl._tracks["c2"].started -= 61.0
    fl.disconnected("c2")
    assert fl.banned.look_up("clientid", "c2") is None
    assert fl._tracks["c2"].count == 1


def test_gc_drops_stale_tracks_only():
    fl = _mk(max_count=10, window=60.0)
    fl.disconnected("old")
    fl.disconnected("fresh")
    fl._tracks["old"].started -= 120.0
    fl.gc()
    assert "old" not in fl._tracks
    assert "fresh" in fl._tracks


def test_flapping_ban_never_downgrades_operator_ban():
    banned = Banned()
    banned.create("clientid", "vip-blocked", by="admin",
                  reason="operator rule", duration=None)  # permanent
    fl = _mk(max_count=2, ban_time=10.0, banned=banned)
    fl.disconnected("vip-blocked")
    fl.disconnected("vip-blocked")
    rule = banned.look_up("clientid", "vip-blocked")
    # the operator's permanent ban survives (create_unless_outlasted)
    assert rule.by == "admin"
    assert rule.until is None


def test_reconnect_storm_bans_flappers_spares_steady():
    """The storm shape the flap-storm scenario drives: a population
    reconnecting at a steady rate stays unbanned, while the hot
    flappers (many disconnects inside one window) all get caught."""
    fl = _mk(max_count=15, window=60.0, ban_time=300.0)
    flappers = [f"flap-{i}" for i in range(20)]
    steady = [f"steady-{i}" for i in range(200)]
    # steady clients: a couple of reconnects each — normal churn
    for cid in steady:
        fl.disconnected(cid)
        fl.disconnected(cid)
    # flappers: a tight crash loop
    for _ in range(15):
        for cid in flappers:
            fl.disconnected(cid)
    for cid in flappers:
        rule = fl.banned.look_up("clientid", cid)
        assert rule is not None and rule.by == "flapping", cid
        assert fl.banned.check(clientid=cid), cid
    for cid in steady:
        assert fl.banned.look_up("clientid", cid) is None, cid
    # gc after the window clears the steady tracks
    now = time.time() + 61.0
    fl.gc(now=now)
    assert not fl._tracks


def test_banned_client_rejected_then_expires():
    fl = _mk(max_count=2, ban_time=0.05)
    fl.disconnected("bounce")
    fl.disconnected("bounce")
    assert fl.banned.check(clientid="bounce")
    time.sleep(0.06)
    # the short auto-ban lapses: the client may reconnect
    assert not fl.banned.check(clientid="bounce")
