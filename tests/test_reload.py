"""Live config reload (docs/OPERATIONS.md, emqx_tpu/reload.py).

The acceptance properties: ``ctl reload <toml>`` applies a
reloadable-knob change without dropping a single connection; a
boot-only edit rejects the WHOLE reload (nothing applied, zones
included) with an explicit per-knob report; the zones-only output
shape of the legacy reload is preserved; and the reloadable/boot_only
classification covers every closed-schema knob and matches the
docs/OPERATIONS.md table.
"""

import dataclasses

from emqx_tpu.config import build_node, load_config
from emqx_tpu.node import Node
from emqx_tpu.reload import apply_reload, classification, diff_config

from tests.mqtt_client import TestClient


def _write(cfg_path, body: str) -> str:
    cfg_path.write_text(body)
    return str(cfg_path)


BASE = (
    '[zones.hot]\nmax_packet_size = 1024\n\n'
    '[[listeners]]\ntype = "tcp"\nport = 0\nzone = "hot"\n\n'
    '[overload]\nlag_warn_ms = 200.0\n\n'
    '[telemetry]\nslow_threshold_ms = 100.0\n'
)


async def test_reload_applies_reloadable_without_drop(tmp_path):
    """The headline property: a reloadable-knob change applies
    atomically while a connected client never notices — and the
    applied values reach the LIVE objects (monitor thresholds, the
    breaker, the ingress wait bound), not just the config dataclass."""
    p = _write(tmp_path / "n.toml", BASE)
    node = build_node(load_config(p))
    await node.start()
    try:
        c = TestClient("rl-live")
        await c.connect(port=node.listeners[0].port)
        _write(tmp_path / "n.toml", (
            '[zones.hot]\nmax_packet_size = 2048\n\n'
            '[[listeners]]\ntype = "tcp"\nport = 0\nzone = "hot"\n\n'
            '[overload]\nlag_warn_ms = 500.0\n'
            'breaker_failures = 7\nbreaker_cooldown_s = 9.0\n'
            'ingress_wait_timeout_s = 11.0\n\n'
            '[telemetry]\nslow_threshold_ms = 250.0\n\n'
            '[dispatch]\npreserialize = false\n\n'
            '[drain]\nwave_size = 5\n'
        ))
        out = node.ctl.run(["reload", p])
        assert "zones reloaded: hot" in out
        assert "rebound" in out
        assert "applied: overload.lag_warn_ms 200.0 -> 500.0" in out
        # the values landed in the RUNNING objects
        assert node.overload.cfg.lag_warn_ms == 500.0
        assert node.broker.breaker.threshold == 7
        assert node.broker.breaker.cooldown_s == 9.0
        assert node.ingress.submit_wait_timeout == 11.0
        assert node.telemetry.config.slow_threshold_ms == 250.0
        assert node.broker.dispatch_config.preserialize is False
        assert node.drain.cfg.wave_size == 5
        assert node.metrics.val("config.reload.applied") >= 5
        # the client never dropped: round-trips still work
        await c.ping()
        await c.publish("rl/t", b"x", qos=1)
        await c.close()
    finally:
        await node.stop()


async def test_reload_rejects_boot_only_atomic(tmp_path):
    """Any boot_only edit rejects the WHOLE reload with a per-knob
    report — nothing applies, zones included."""
    p = _write(tmp_path / "n.toml", BASE)
    node = build_node(load_config(p))
    await node.start()
    try:
        _write(tmp_path / "n.toml", (
            '[zones.hot]\nmax_packet_size = 4096\n\n'
            '[[listeners]]\ntype = "tcp"\nport = 0\nzone = "hot"\n\n'
            '[node]\nloops = 4\n\n'
            '[overload]\nlag_warn_ms = 900.0\n\n'
            '[matcher]\nmax_levels = 8\n'
        ))
        out = node.ctl.run(["reload", p])
        assert "reload rejected" in out
        assert "node.loops" in out and "matcher.max_levels" in out
        # NOTHING applied: zone, reloadable knob, all untouched
        from emqx_tpu.zone import get_zone
        assert get_zone("hot").max_packet_size == 1024
        assert node.overload.cfg.lag_warn_ms == 200.0
        assert node.router.config.max_levels == 16
        assert node.metrics.val("config.reload.rejected") >= 2
        assert node.metrics.val("config.reload.applied") == 0
    finally:
        await node.stop()


async def test_reload_inactive_sections_are_boot_only(tmp_path):
    """Enabling a subsystem that was never built (durability on a
    volatile node, cluster without a transport) is boot_only by
    definition; listener topology diffs are boot_only too."""
    p = _write(tmp_path / "n.toml", BASE)
    node = build_node(load_config(p))
    await node.start()
    try:
        _write(tmp_path / "n.toml", (
            '[zones.hot]\nmax_packet_size = 1024\n\n'
            '[[listeners]]\ntype = "tcp"\nport = 0\nzone = "hot"\n\n'
            '[[listeners]]\ntype = "tcp"\nport = 1884\nzone = "hot"\n\n'
            '[overload]\nlag_warn_ms = 200.0\n\n'
            '[telemetry]\nslow_threshold_ms = 100.0\n\n'
            '[durability]\nenabled = true\n'
        ))
        out = node.ctl.run(["reload", p])
        assert "reload rejected" in out
        assert "durability.enabled" in out
        assert "listeners.*" in out
        assert node.durability is None
    finally:
        await node.stop()


async def test_reload_absent_sections_untouched(tmp_path):
    """A section absent from the file means "not configured here" —
    the running values survive (never a reset-to-defaults)."""
    p = _write(tmp_path / "n.toml", BASE)
    node = build_node(load_config(p))
    await node.start()
    try:
        # file WITHOUT [overload]/[telemetry]: no diff for them
        _write(tmp_path / "n.toml", (
            '[zones.hot]\nmax_packet_size = 1024\n\n'
            '[[listeners]]\ntype = "tcp"\nport = 0\nzone = "hot"\n'
        ))
        out = node.ctl.run(["reload", p])
        assert "rejected" not in out
        assert node.overload.cfg.lag_warn_ms == 200.0
    finally:
        await node.stop()


async def test_reload_zone_only_output_shape(tmp_path):
    """The legacy zones-only reload keeps its exact output shape
    (zones reloaded / listeners rebound / stale), and a broken file
    still rejects whole with zones untouched."""
    p = _write(tmp_path / "n.toml", BASE)
    node = build_node(load_config(p))
    await node.start()
    try:
        _write(tmp_path / "n.toml", BASE.replace("1024", "2048"))
        out = node.ctl.run(["reload", p])
        assert out.startswith("zones reloaded: hot")
        assert "listeners rebound: tcp:0" in out
        # stale zone reporting preserved
        _write(tmp_path / "n.toml", (
            '[zones.other]\nmax_inflight = 5\n\n'
            '[[listeners]]\ntype = "tcp"\nport = 0\nzone = "other"\n'
        ))
        out = node.ctl.run(["reload", p])
        assert "stale" in out and "hot" in out
        # broken file: error text, nothing changes
        _write(tmp_path / "n.toml", '[zones.hot]\nno_such = 1\n')
        out = node.ctl.run(["reload", p])
        assert "error" in out.lower()
        # usage string describes the diff-based behavior now
        assert "diff" in node.ctl.usage()
    finally:
        await node.stop()


async def test_reload_matcher_delta_flip_applies(tmp_path):
    """matcher.delta is reloadable through Router.set_delta (the
    runtime flip PR 7 built) — the router actually changes mode."""
    p = _write(tmp_path / "n.toml", BASE)
    node = build_node(load_config(p))
    await node.start()
    try:
        assert node.router.config.delta
        _write(tmp_path / "n.toml",
               BASE + '\n[matcher]\ndelta = false\n')
        out = node.ctl.run(["reload", p])
        assert "applied: matcher.delta" in out
        assert not node.router.config.delta
        # the flip went through set_delta: no delta automaton is
        # published anymore
        assert node.router.delta_info().get("enabled") in (False,
                                                          None) \
            or not node.router.config.delta
    finally:
        await node.stop()


# -- classification integrity --------------------------------------------

def test_classification_covers_every_knob():
    """Every closed-schema dataclass field is classified, RELOADABLE
    names only real fields, and the [node] pseudo-section matches
    config.parse_config's key tuple."""
    table = classification()
    from emqx_tpu.reload import _sections
    for section, cls in _sections().items():
        fields = {f.name for f in dataclasses.fields(cls)}
        assert set(table[section]) == fields, section
        reloadable = getattr(cls, "RELOADABLE", frozenset())
        assert reloadable <= fields, (
            f"[{section}] RELOADABLE names unknown knobs: "
            f"{reloadable - fields}")
    assert set(table["node"]) == {
        "name", "sys_interval", "cookie", "cluster_port",
        "load_default_modules", "loops", "frame"}


def test_classification_matches_operations_doc():
    """The docs/OPERATIONS.md knob table is generated from
    classification() — regenerate and require every row verbatim
    (the lint-checked-docs satellite)."""
    doc = open("docs/OPERATIONS.md").read()
    for section, knobs in classification().items():
        r = ", ".join(f"`{k}`" for k, v in sorted(knobs.items())
                      if v == "reloadable") or "—"
        b = ", ".join(f"`{k}`" for k, v in sorted(knobs.items())
                      if v == "boot_only") or "—"
        row = f"| `[{section}]` | {r} | {b} |"
        assert row in doc, (
            f"docs/OPERATIONS.md knob table out of date for "
            f"[{section}]: expected row\n{row}")


def test_diff_config_programmatic_node(tmp_path):
    """diff_config works against a node never booted from a file
    (boot_config None): sections diff against live objects, listener
    topology silently skips (nothing to compare against)."""
    from emqx_tpu.config import parse_config
    node = Node(boot_listeners=False)
    cfg = parse_config({"overload": {"lag_warn_ms": 777.0},
                        "listeners": [{"type": "tcp", "port": 1883}]})
    changes = diff_config(node, cfg)
    knobs = {c.knob: c.kind for c in changes}
    assert knobs.get("overload.lag_warn_ms") == "reloadable"
    assert "listeners.*" not in knobs
    report = apply_reload(node, cfg)
    assert [a["knob"] for a in report["applied"]] \
        == ["overload.lag_warn_ms"]
    assert node.overload_config.lag_warn_ms == 777.0
