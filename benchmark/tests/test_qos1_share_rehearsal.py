"""What the queue's next two cells need of the generator (PR 46),
rehearsed at toy size on the CPU: subscribers that acknowledge at the
configuration's ``deliver_qos``, and the expectation of a shared
group. Each comes with its control: subscribers that keep their
PUBACKs back, and a delivery doubled on one socket of the group, both
of which a run must not survive. No accepted cell uses either: every
accepted configuration reads ``deliver_qos`` 0 and holds no ``$share``
filter, and for those not a byte of what is sent or counted changed."""

import json
import os

import pytest

import loadgen
import reference
import sabotage
import toy
from test_rehearsal import _moved, _run, _spec

FAN = ["fan/g0", "fan/g1", "fan/g2", "fan/g3", "fan/g4"]


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return toy.make(str(tmp_path_factory.mktemp("toy_qos1_share")))


def _put(bench_dir, kind, name, obj):
    path = os.path.join(bench_dir, kind, name + ".json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=2)


def _add_cell(bench_dir, config, edit, traffic, mix):
    """What a later PR does with new files and entries alone: the toy
    ``fanout_1k`` again as ``config`` with ``edit`` applied, a traffic
    mix ``traffic``, and the cell of the two."""
    spec_path = os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json")
    spec = _spec(bench_dir)
    cell = f"{config}.{traffic}"
    if any(w["name"] == cell for w in spec["workloads"]):
        return cell
    with open(os.path.join(bench_dir, "configs", "fanout_1k.json")) as f:
        cfg = json.load(f)
    cfg["name"] = config
    edit(cfg)
    _put(bench_dir, "configs", config, cfg)
    _put(bench_dir, "traffic", traffic, dict(mix, name=traffic))
    _put(bench_dir, "workloads", cell, {
        "config": config, "traffic": traffic, "chips": 1, "overrides": {},
        "why": "a later PR's cell", "who": "a test"})
    if not any(c["name"] == config for c in spec["configs"]):
        base = next(c for c in spec["configs"] if c["name"] == "fanout_1k")
        spec["configs"].append(dict(
            base, name=config, file=f"benchmark/configs/{config}.json"))
    spec["workloads"].append({"name": cell, "config": config,
                              "traffic": traffic, "chips": 1,
                              "why": "a later PR's cell"})
    with open(spec_path, "w", encoding="utf-8") as f:
        json.dump(spec, f, indent=2)
    return cell


# -- subscribers that acknowledge --------------------------------------------

#: every message is its burst's fence: published at QoS 1, one at a
#: time a publisher, so every delivery is a QoS 1 delivery
QOS1_MIX = {"loop": "flood", "publishers": 5, "burst": 1,
            "subscriber_procs": 2}


def _qos1(cfg):
    cfg["guarantees"].update(publish_qos=1, deliver_qos=1)


def test_every_accepted_configuration_still_delivers_at_qos_0():
    spec = _spec(toy.BENCH)
    for c in spec["configs"]:
        with open(os.path.join(toy.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["guarantees"]["deliver_qos"] == 0, c["name"]
        assert not any(f.startswith("$share/") for g in cfg["sockets"]
                       for f in g["filters"]), c["name"]
    for name in os.listdir(os.path.join(toy.BENCH, "traffic")):
        with open(os.path.join(toy.BENCH, "traffic", name)) as f:
            assert "subscriber_acks" not in json.load(f), name


def test_qos1_deliveries_are_acknowledged_once_each(bench_dir, capsys):
    cell = _add_cell(bench_dir, "toy_qos1", _qos1, "flood_qos1", QOS1_MIX)
    out, lines = _run(bench_dir, capsys, cell, seed=2147484001)
    # every message once a socket, none above the grant, none sent a
    # second time (a DUP counts as bad_qos, a second copy as surplus)
    assert out["correct"] is True and out["failed"] == 0, \
        "\n".join(lines[-15:])
    assert out["attempted"] > 40 * 33  # past a full inflight window each
    assert not _moved(lines)


def test_subscribers_that_withhold_their_pubacks_fail_the_run(
        bench_dir, capsys):
    """The control: the window of 32 unacknowledged deliveries a
    session fills in the warm rounds, and what follows stays queued."""
    cell = _add_cell(bench_dir, "toy_qos1", _qos1, "flood_qos1_noack",
                     dict(QOS1_MIX, subscriber_acks=False))
    out, lines = _run(bench_dir, capsys, cell, seed=2147484002)
    assert out["correct"] is False, "\n".join(lines[-15:])
    moved = _moved(lines)
    assert len(moved) == 1 and "socket deliveries missing" in moved[0]
    assert out["failed"] > 0


# -- a shared group -------------------------------------------------------------

SHARE_MIX = {"loop": "flood", "publishers": 5, "burst": 8,
             "subscriber_procs": 1}


def _share(cfg):
    cfg["sockets"] = [
        {"count": 4, "filters": ["$share/grp/fan/#"]},
        {"count": 6, "first": 4, "filters": FAN}]
    cfg["guarantees"]["delivery"] = (
        "every broadcast message arrives exactly once on each plain "
        "subscriber socket and exactly once on one socket of the shared "
        "group, never on two, never on none")


def test_a_shared_group_gets_each_message_once_between_its_sockets(
        bench_dir, capsys):
    cell = _add_cell(bench_dir, "toy_share", _share, "flood_share",
                     SHARE_MIX)
    out, lines = _run(bench_dir, capsys, cell, seed=2147484004)
    assert out["correct"] is True and out["failed"] == 0, \
        "\n".join(lines[-15:])
    assert not _moved(lines)


def test_a_doubled_delivery_in_the_group_fails_the_run(bench_dir, capsys):
    """The control: socket 0 is one of the group's; one message the
    broker queued for it is written twice."""
    cell = _add_cell(bench_dir, "toy_share", _share, "flood_share",
                     SHARE_MIX)
    out, lines = _run(bench_dir, capsys, cell, seed=2147484005,
                      sabotage=sabotage.duplicate_delivery)
    assert out["correct"] is False, "\n".join(lines[-15:])
    moved = _moved(lines)
    assert len(moved) == 1 and "socket deliveries missing, surplus" \
        in moved[0]
    assert out["failed"] == 1


# -- the rule, without a broker --------------------------------------------------


def _plan(sockets, procs=1, **guarantees):
    return loadgen.Plan({
        "seed": 7, "dir": "/nonexistent",
        "traffic": {"publishers": 2, "loop": "flood", "burst": 4,
                    "subscriber_procs": procs},
        "config": {
            "population": {"kind": "mixed_tree", "filters": 50,
                           "levels": 3, "words_per_level": 4,
                           "mix": {"literal": 0.6, "plus": 0.25,
                                   "hash": 0.15}},
            "publish_topics": {"law": "round_robin", "topics": FAN,
                               "pool": 10},
            "payload_bytes": 64, "sockets": sockets,
            "guarantees": dict({"deliver_qos": 0, "publish_qos": 0},
                               **guarantees)}})


def test_share_of_is_the_plain_rule():
    assert reference.share_of("$share/g/a/+/#") == ("g", "a/+/#")
    assert reference.share_of("a/$share/b") == (None, "a/$share/b")
    assert reference.share_of("fan/g0") == (None, "fan/g0")
    for bad in ("$share/g", "$share//a", "$share/g/"):
        with pytest.raises(ValueError):
            reference.share_of(bad)


def test_the_plan_refuses_a_group_it_cannot_compare():
    grp = [{"count": 4, "filters": ["$share/g/fan/#"]}]
    assert _plan(grp).groups == {"g": ([0, 1, 2, 3], ["fan/#"])}
    with pytest.raises(ValueError, match="split over subscriber"):
        _plan(grp, procs=2)
    # four sockets, four processes apart, are one process's again
    assert _plan([{"count": 2, "filters": ["$share/g/fan/#"]},
                  {"count": 2, "first": 2, "filters": FAN}],
                 procs=1).groups["g"][0] == [0, 1]
    with pytest.raises(ValueError, match="two shared groups"):
        _plan([{"count": 1, "filters": ["$share/g/a", "$share/h/a"]}])
    with pytest.raises(ValueError, match="the same filters"):
        _plan([{"count": 1, "filters": ["$share/g/a"]},
               {"count": 1, "first": 1, "filters": ["$share/g/b"]}])
    with pytest.raises(ValueError, match="QoS 1 alone"):
        _plan(grp, deliver_qos=2)
    assert (_plan(grp).low_qos, _plan(grp, deliver_qos=1).low_qos,
            _plan(grp, deliver_qos=1, publish_qos=1).low_qos) == (0, 0, 1)


@pytest.fixture()
def group_subs():
    plan = _plan([{"count": 3, "filters": ["$share/g/fan/#"]},
                  {"count": 2, "first": 3, "filters": ["fan/g0"]}])
    return loadgen.Subscribers(plan, 0, 1)


def _due(subs, sent):
    """(publisher, seq) of every message of the phase, and of those on
    fan/g0: publisher p starts at pool position p * 10 // 2."""
    pool = subs.plan.pool()
    msgs = [(p, s) for p, n in enumerate(sent) for s in range(n)]
    g0 = [(p, s) for p, s in msgs
          if pool[(subs.plan.base(p, [0, 0]) + s) % 10] == "fan/g0"]
    return msgs, g0


def test_the_groups_expectation_once_on_one_socket(group_subs, tmp_path):
    subs, sent = group_subs, [7, 6]
    msgs, g0 = _due(subs, sent)
    assert g0 and len(g0) < len(msgs)

    def run(deliveries):
        subs.begin(0, 0.0, 1.0, [0, 0])
        for k, ids in deliveries.items():
            subs.ids[k].extend((p << 32) | s for p, s in ids)
            subs.received += len(ids)
        out = subs.finish({"sent": sent, "quiesce_s": 0.0}, str(tmp_path))
        return out["attempted"], out["missing"], out["surplus"]

    total = len(msgs) + 2 * len(g0)
    # any split of the group's messages over its sockets is right
    spread = {0: msgs[0::3], 1: msgs[1::3], 2: msgs[2::3], 3: g0, 4: g0}
    assert run(spread) == (total, 0, 0)
    assert run({0: msgs, 1: [], 2: [], 3: g0, 4: g0}) == (total, 0, 0)
    # on two sockets of the group: one too many
    assert run({**spread, 1: spread[1] + [msgs[0]]}) == (total, 0, 1)
    # on none
    assert run({**spread, 0: spread[0][1:]}) == (total, 1, 0)
    # a plain socket's copy that went to a socket of the group instead
    # is missing there and one too many here
    assert run({**spread, 3: g0[1:], 0: spread[0] + [g0[0]]}) \
        == (total, 1, 1)


# -- the QoS a delivery bears, without a broker ----------------------------------


def _publish(topic, qos, pid, body, dup=False):
    head = loadgen.publish_prefix(topic, len(body), qos)
    if dup:
        head = bytes([head[0] | 0x08]) + head[1:]
    return head + (pid.to_bytes(2, "big") if qos else b"") + body


@pytest.mark.parametrize("deliver,publish,acks,frames,bad,acked", [
    # the accepted cells: QoS 0 granted, any QoS bit is wrong, no byte sent
    (0, 0, True, [(0, 0, False), (1, 7, False)], 1, []),
    # QoS 1 granted and published: each answered with its PUBACK
    (1, 1, True, [(1, 7, False), (1, 8, False)], 0, [7, 8]),
    # sent a second time (DUP): counted, not answered
    (1, 1, True, [(1, 7, False), (1, 7, True)], 1, [7]),
    # under the grant, and QoS 2, which nobody granted
    (1, 1, True, [(0, 0, False), (2, 9, False)], 2, []),
    # a fence among QoS 0 publishes at a grant of 1: both are right
    (1, 0, True, [(0, 0, False), (1, 5, False)], 0, [5]),
    # the control's switch: counted as right, never answered
    (1, 1, False, [(1, 7, False)], 0, []),
])
def test_the_qos_a_delivery_bears(deliver, publish, acks, frames, bad,
                                  acked):
    import socket

    plan = _plan([{"count": 1, "filters": ["fan/g0"]}],
                 deliver_qos=deliver, publish_qos=publish)
    plan.sub_acks = acks
    subs = loadgen.Subscribers(plan, 0, 1)
    subs.left, subs.unsent = [b""], [b""]
    subs.begin(0, 0.0, 1.0, [0, 0])
    ours, brokers = socket.socketpair()
    ours.setblocking(False)
    pos = subs.plan.pool().index("fan/g0")
    body = loadgen.HEADER.pack(0, pos, 0, 1, 0.0) + b"x" * 8
    brokers.sendall(b"".join(_publish("fan/g0", q, pid, body, dup)
                             for q, pid, dup in frames))
    subs._on_data(0, ours)
    assert (subs.received, subs.bad_qos, subs.bad_topic) == (
        len(frames), bad, 0)
    brokers.setblocking(False)
    try:
        back = brokers.recv(64)
    except BlockingIOError:
        back = b""
    assert back == b"".join(b"\x40\x02" + pid.to_bytes(2, "big")
                            for pid in acked)
    ours.close()
    brokers.close()
