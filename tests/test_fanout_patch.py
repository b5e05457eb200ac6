"""The fan-out tables under membership change
(``broker_helper.FanoutManager.state``): inside one automaton epoch a
change costs the rows it changes, and what the patched tables say is
what tables built from scratch say. The plain reference is the whole
build (``FanoutManager._build`` on a manager of its own): every filter
of the id map looked up in ``rows``.

The id map here is kept the way ``router.py`` keeps the one it
publishes: append-only and tombstone-only inside an epoch, compacted
(ids recycled) when the epoch moves."""

import random

import numpy as np
import pytest

from emqx_tpu.broker_helper import _PATCH_ROWS, FanoutManager
from emqx_tpu.metrics import FANOUT_METRICS, Metrics
from emqx_tpu.telemetry import Telemetry, TelemetryConfig


class _Sub:
    """A subscriber: anything hashable."""


def _rows(fan, n):
    """The table as ``row_pairs`` reads it: each id's members."""
    pairs, subs = np.asarray(fan.row_pairs), np.asarray(fan.sub_ids)
    return [tuple(subs[a:b]) for a, b in pairs[:n]]


def _scratch(fm, epoch, id_map):
    """The tables a manager with the same memberships, the same
    registry and the same retained capacities builds whole."""
    ref = FanoutManager(threshold=fm.threshold, use_device=False)
    ref.rows = {f: set(r) for f, r in fm.rows.items()}
    ref.registry = fm.registry
    ref._caps = dict(fm._caps)
    return ref.state(epoch, id_map)


def _same(fm, st, epoch, id_map):
    want = _scratch(fm, epoch, id_map)
    assert (st is None) == (want is None)
    if st is None:
        return
    assert st.epoch == epoch and st.big_fids == want.big_fids
    assert (st.fan is None) == (want.fan is None)
    if st.fan is not None:
        # array for array: the capacities, and every row's members
        for a, b in zip(st.fan[:2] + (st.fan.row_pairs,),
                        want.fan[:2] + (want.fan.row_pairs,)):
            assert np.asarray(a).shape == np.asarray(b).shape
        assert _rows(st.fan, len(id_map)) == _rows(want.fan, len(id_map))
        # nothing is reachable past the map, and the device's copy is
        # the host mirror's
        pairs = np.asarray(st.fan.row_pairs)
        assert (pairs[len(id_map):, 0] == pairs[len(id_map):, 1]).all()
        if fm._mirror is not None:
            assert np.array_equal(pairs, fm._mirror[0])
            assert np.array_equal(np.asarray(st.fan.sub_ids),
                                  fm._mirror[1])
    assert (st.bm is None) == (want.bm is None)
    if st.bm is not None:
        assert np.array_equal(np.asarray(st.bm.bitmaps),
                              np.asarray(want.bm.bitmaps))
        assert np.array_equal(np.asarray(st.bm.big_row),
                              np.asarray(want.bm.big_row))


class _Routes:
    """The router's half: filter ids, append-only inside an epoch."""

    def __init__(self):
        self.epoch = 1
        self.id_map = []
        self.live = {}

    def add(self, f):
        if f not in self.live:
            self.live[f] = len(self.id_map)
            self.id_map.append(f)

    def drop(self, f):
        fid = self.live.pop(f, None)
        if fid is not None:
            self.id_map[fid] = None

    def flatten(self):
        """A new epoch: a new map object, freed ids recycled."""
        self.epoch += 1
        self.id_map = sorted(self.live, key=self.live.get)
        self.live = {f: i for i, f in enumerate(self.id_map)}


@pytest.mark.parametrize("use_device", [False, True])
@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_patched_tables_equal_tables_built_from_scratch(seed, use_device):
    """Seeded subscribe / unsubscribe / release over a few thousand
    filters: rows cross the threshold into bitmaps and back, filters
    lose their last subscriber, ids recycle over an epoch."""
    rng = random.Random(seed)
    fm = FanoutManager(threshold=6, use_device=use_device)
    rt = _Routes()
    subs = [_Sub() for _ in range(120)]
    held = {s: set() for s in subs}
    for i in range(3000):
        f = f"res/{i}"
        fm.subscribe(f, subs[0])
        held[subs[0]].add(f)
        rt.add(f)
    st = fm.state(rt.epoch, rt.id_map)
    _same(fm, st, rt.epoch, rt.id_map)
    assert (fm.rebuilds, fm.patches) == (1, 0)
    hot = [f"hot/{i}" for i in range(12)]   # these fill past 6
    for step in range(600):
        s = subs[rng.randrange(1, len(subs))]
        roll = rng.random()
        if roll < 0.45:
            f = rng.choice(hot) if rng.random() < 0.4 \
                else f"dev/{rng.randrange(400)}/cmd/#"
            fm.subscribe(f, s)
            held[s].add(f)
            rt.add(f)
        elif roll < 0.85 and held[s]:
            f = rng.choice(sorted(held[s]))
            fm.unsubscribe(f, s)
            held[s].discard(f)
            if not fm.members(f):
                rt.drop(f)
        elif roll < 0.95:
            for f in sorted(held[s]):
                fm.unsubscribe(f, s)
                if not fm.members(f):
                    rt.drop(f)
            held[s].clear()
            fm.release(s)
        else:
            rt.flatten()
        if step % 4 == 0:
            st = fm.state(rt.epoch, rt.id_map)
            _same(fm, st, rt.epoch, rt.id_map)
    assert fm.patches > 20 and fm.rows_patched >= fm.patches
    assert any(len(r) > 6 for r in fm.rows.values()) or fm.rebuilds > 2


def _seeded(n=2000, threshold=1024, use_device=False):
    fm = FanoutManager(threshold=threshold, use_device=use_device)
    rt = _Routes()
    sink = _Sub()
    for i in range(n):
        fm.subscribe(f"res/{i}/+", sink)
        rt.add(f"res/{i}/+")
    st = fm.state(rt.epoch, rt.id_map)
    assert (fm.rebuilds, fm.patches) == (1, 0)
    return fm, rt, st, sink


def test_unchanged_membership_is_one_compare():
    fm, rt, st, _sink = _seeded()
    assert fm.state(rt.epoch, rt.id_map) is st
    assert (fm.rebuilds, fm.patches, fm.rows_patched) == (1, 0, 0)


def test_a_subscribe_and_an_unsubscribe_patch_one_row_each():
    fm, rt, st, _sink = _seeded()
    dev = _Sub()
    fm.subscribe("dev/7/cmd/#", dev)
    rt.add("dev/7/cmd/#")
    st2 = fm.state(rt.epoch, rt.id_map)
    assert st2 is not st and (fm.rebuilds, fm.patches) == (1, 1)
    assert fm.rows_patched == 1
    fid = rt.live["dev/7/cmd/#"]
    assert _rows(st2.fan, fid + 1)[fid] == (fm.registry.sid(dev),)
    # the state a batch in flight holds is as it was
    assert _rows(st.fan, fid + 1)[fid] == ()
    fm.unsubscribe("dev/7/cmd/#", dev)
    rt.drop("dev/7/cmd/#")
    st3 = fm.state(rt.epoch, rt.id_map)
    assert (fm.rebuilds, fm.patches, fm.rows_patched) == (1, 2, 2)
    assert _rows(st3.fan, fid + 1)[fid] == ()
    _same(fm, st3, rt.epoch, rt.id_map)


def test_a_filter_that_comes_back_takes_its_new_id_and_leaves_the_old():
    fm, rt, _st, _sink = _seeded()
    dev = _Sub()
    for _ in range(3):     # a session, thrice: the id moves each time
        fm.subscribe("dev/1/cmd/#", dev)
        rt.add("dev/1/cmd/#")
        fm.state(rt.epoch, rt.id_map)
        fm.unsubscribe("dev/1/cmd/#", dev)
        rt.drop("dev/1/cmd/#")
    fm.subscribe("dev/1/cmd/#", dev)     # dropped and back between syncs
    rt.add("dev/1/cmd/#")
    st = fm.state(rt.epoch, rt.id_map)
    rows = _rows(st.fan, len(rt.id_map))
    assert [i for i, r in enumerate(rows[2000:], 2000) if r] \
        == [rt.live["dev/1/cmd/#"]]
    assert fm.rebuilds == 1
    _same(fm, st, rt.epoch, rt.id_map)


def test_the_route_may_come_before_or_after_the_membership():
    fm, rt, _st, _sink = _seeded()
    a, b = _Sub(), _Sub()
    rt.add("x/a")                       # the route first: no member yet
    st = fm.state(rt.epoch, rt.id_map)
    assert _rows(st.fan, len(rt.id_map))[rt.live["x/a"]] == ()
    fm.subscribe("x/a", a)
    st = fm.state(rt.epoch, rt.id_map)
    assert _rows(st.fan, len(rt.id_map))[rt.live["x/a"]] \
        == (fm.registry.sid(a),)
    fm.subscribe("x/b", b)              # the membership first
    st = fm.state(rt.epoch, rt.id_map)
    rt.add("x/b")
    st = fm.state(rt.epoch, rt.id_map)
    assert _rows(st.fan, len(rt.id_map))[rt.live["x/b"]] \
        == (fm.registry.sid(b),)
    assert fm.rebuilds == 1
    _same(fm, st, rt.epoch, rt.id_map)


def test_a_row_that_crosses_the_threshold_rebuilds_and_comes_back():
    fm, rt, _st, _sink = _seeded(threshold=4)
    subs = [_Sub() for _ in range(6)]
    rt.add("hot/t")
    for s in subs[:4]:
        fm.subscribe("hot/t", s)
    st = fm.state(rt.epoch, rt.id_map)
    assert fm.rebuilds == 1 and st.bm is None
    fm.subscribe("hot/t", subs[4])      # five members: a bitmap row
    st = fm.state(rt.epoch, rt.id_map)
    assert fm.rebuilds == 2 and st.big_fids == {rt.live["hot/t"]}
    _same(fm, st, rt.epoch, rt.id_map)
    fm.subscribe("hot/t", subs[5])      # a bitmap row changes: whole
    st = fm.state(rt.epoch, rt.id_map)
    assert fm.rebuilds == 3
    _same(fm, st, rt.epoch, rt.id_map)
    other = _Sub()                      # a CSR row beside it: a patch
    fm.subscribe("cold/t", other)
    rt.add("cold/t")
    st = fm.state(rt.epoch, rt.id_map)
    assert fm.rebuilds == 3 and st.bm is not None
    _same(fm, st, rt.epoch, rt.id_map)
    for s in subs[3:]:                  # back under the threshold
        fm.unsubscribe("hot/t", s)
    st = fm.state(rt.epoch, rt.id_map)
    assert fm.rebuilds == 4 and not st.big_fids and st.bm is None
    _same(fm, st, rt.epoch, rt.id_map)


def test_an_id_recycled_over_an_epoch_bears_its_new_filter():
    fm, rt, _st, _sink = _seeded()
    a, b = _Sub(), _Sub()
    fm.subscribe("old/f", a)
    rt.add("old/f")
    fm.state(rt.epoch, rt.id_map)
    fid = rt.live["old/f"]
    fm.unsubscribe("old/f", a)
    rt.drop("old/f")
    fm.subscribe("new/f", b)
    rt.add("new/f")
    rt.flatten()                        # the freed id goes to new/f
    assert rt.live["new/f"] == fid
    st = fm.state(rt.epoch, rt.id_map)
    assert fm.rebuilds == 2             # a new epoch builds whole
    assert _rows(st.fan, fid + 1)[fid] == (fm.registry.sid(b),)
    _same(fm, st, rt.epoch, rt.id_map)
    fm.subscribe("new/f", a)            # and patches go on from there
    st = fm.state(rt.epoch, rt.id_map)
    assert (fm.rebuilds, fm.patches) == (2, 2)
    _same(fm, st, rt.epoch, rt.id_map)


def test_ids_past_the_filter_capacity_rebuild_at_twice_the_size():
    fm, rt, st, _sink = _seeded(n=2040)
    assert np.asarray(st.fan.row_pairs).shape[0] == 2048
    devs = [_Sub() for _ in range(12)]
    for i, d in enumerate(devs):
        fm.subscribe(f"dev/{i}/cmd/#", d)
        rt.add(f"dev/{i}/cmd/#")
        st = fm.state(rt.epoch, rt.id_map)
    assert np.asarray(st.fan.row_pairs).shape[0] == 4096
    # seven entries fit behind the 2,040; the eighth rebuilds (the
    # entries double), the ninth id is the 2,049th (the ids double)
    assert fm.rebuilds == 3 and fm.patches == 10
    _same(fm, st, rt.epoch, rt.id_map)


def test_a_table_out_of_room_behind_its_entries_rebuilds():
    fm, rt, st, _sink = _seeded(n=2000)
    room = np.asarray(st.fan.sub_ids).shape[0] - 1 - 2000
    dev = _Sub()
    rt.add("dev/0/cmd/#")
    for i in range(room + 1):           # each session writes one entry
        fm.subscribe("dev/0/cmd/#", dev)
        st = fm.state(rt.epoch, rt.id_map)
        fm.unsubscribe("dev/0/cmd/#", dev)
    assert fm.rebuilds == 2 and fm.patches == room
    assert fm._tail == 2001             # the build compacted the runs
    _same(fm, fm.state(rt.epoch, rt.id_map), rt.epoch, rt.id_map)


def test_many_changes_in_one_sync_take_several_chunks():
    fm, rt, _st, _sink = _seeded(n=5000, threshold=8)   # room for all
    devs = [_Sub() for _ in range(2 * _PATCH_ROWS + 17)]
    for i, d in enumerate(devs):
        for j in range(1 + i % 7):      # rows of one to seven members
            fm.subscribe(f"dev/{i}/cmd/#", devs[(i + j) % len(devs)])
        rt.add(f"dev/{i}/cmd/#")
    st = fm.state(rt.epoch, rt.id_map)
    assert (fm.rebuilds, fm.patches) == (1, 1)
    assert fm.rows_patched == len(devs)
    _same(fm, st, rt.epoch, rt.id_map)


def test_no_change_is_remembered_before_there_are_tables():
    fm = FanoutManager(use_device=False)
    sink = _Sub()
    for i in range(5000):               # a node seeding at boot
        fm.subscribe(f"res/{i}", sink)
    assert not fm._changed
    fm.state(1, [f"res/{i}" for i in range(5000)])
    fm.subscribe("res/1", _Sub())
    assert fm._changed == {"res/1"}
    fm.invalidate_device()              # device loss: whole again
    assert not fm._changed and fm._mirror is None
    fm.subscribe("res/2", _Sub())
    assert not fm._changed
    st = fm.state(1, [f"res/{i}" for i in range(5000)])
    assert fm.rebuilds == 2 and fm.patches == 0
    _same(fm, st, 1, [f"res/{i}" for i in range(5000)])


def test_the_last_subscriber_gone_leaves_no_tables():
    fm = FanoutManager(use_device=False)
    a = _Sub()
    fm.subscribe("a/b", a)
    assert fm.state(1, ["a/b"]) is not None
    fm.unsubscribe("a/b", a)
    assert fm.state(1, [None]) is None
    fm.subscribe("a/c", a)
    st = fm.state(1, [None, "a/c"])
    assert _rows(st.fan, 2) == [(), (fm.registry.sid(a),)]


class _CountedDict(dict):
    gets = 0

    def get(self, *a):
        _CountedDict.gets += 1
        return super().get(*a)


class _CountedList(list):
    reads = 0

    def __getitem__(self, i):
        _CountedList.reads += 1
        return super().__getitem__(i)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


def test_one_change_at_200k_filters_visits_the_changed_rows_alone():
    """Counted, not timed: the whole build looks every filter of the
    id map up in ``rows``; a patch looks up what changed."""
    n = 200_000
    fm = FanoutManager(use_device=False)
    sink = _Sub()
    id_map = _CountedList(f"res/{i}/+" for i in range(n))
    for f in list.__iter__(id_map):
        fm.subscribe(f, sink)
    fm.rows = _CountedDict(fm.rows)
    _CountedDict.gets = _CountedList.reads = 0
    fm.state(1, id_map)
    assert _CountedDict.gets == n and _CountedList.reads == n
    dev = _Sub()
    for change in (lambda: (fm.subscribe("dev/1/cmd/#", dev),
                            id_map.append("dev/1/cmd/#")),
                   lambda: fm.subscribe("res/7/+", dev),
                   lambda: (fm.unsubscribe("dev/1/cmd/#", dev),
                            id_map.__setitem__(n, None))):
        change()
        _CountedDict.gets = _CountedList.reads = 0
        before = fm.rows_patched
        fm.state(1, id_map)
        assert fm.rows_patched - before == 1
        assert _CountedDict.gets <= 2 and _CountedList.reads <= 3
    assert (fm.rebuilds, fm.patches) == (1, 3)
    _CountedDict.gets = _CountedList.reads = 0
    fm.state(1, id_map)                 # and nothing where none did
    assert _CountedDict.gets == _CountedList.reads == 0


@pytest.mark.parametrize("enabled", [True, False])
def test_the_syncs_are_counted_while_telemetry_is_on(enabled):
    m = Metrics()
    fm, rt, _st, _sink = _seeded()
    fm.telemetry = Telemetry(TelemetryConfig(enabled=enabled), metrics=m)
    dev = _Sub()
    fm.subscribe("dev/1/cmd/#", dev)
    rt.add("dev/1/cmd/#")
    fm.state(rt.epoch, rt.id_map)       # a patch
    fm.state(rt.epoch, rt.id_map)       # nothing
    rt.flatten()
    fm.state(rt.epoch, rt.id_map)       # a rebuild
    got = {k: m.val(k) for k in FANOUT_METRICS}
    if enabled:
        assert got["fanout.patches"] == got["fanout.rebuilds"] == 1
        assert got["fanout.sync.ns"] > 0
    else:
        assert not any(got.values())
    assert fm.stats()["fanout.patches"] == 1
    assert fm.stats()["fanout.rebuilds"] == 2
