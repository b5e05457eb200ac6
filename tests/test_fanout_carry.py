"""The fan-out tables over a compaction's swap (PR 42,
``broker_helper.FanoutManager.carry``, docs/DELTA.md "Fan-out
tables"): a merge of the delta automaton moves the automaton epoch and
keeps every filter's id, so the tables go over to the new epoch as
they are; the ids a merge gives back are taken by later route adds in
place, which the published map's ``reused`` log says. What the carried
and patched tables hold is what a whole build (``_build`` on a manager
of its own) holds, array for array, after every step of seeded
subscribe / unsubscribe / recycle sequences on a real ``Router`` whose
delta folds every few adds; and a merge makes the next sync look up
the rows that changed and no other."""

import random
import time

import numpy as np
import pytest

from emqx_tpu.broker import Broker
from emqx_tpu.broker_helper import FanoutManager
from emqx_tpu.router import IdMap, MatcherConfig, Router

from test_fanout_patch import _rows, _same


class _Sub:
    def deliver(self, topic_filter, msg):
        pass


def _broker(**kw):
    kw.setdefault("device_min_filters", 0)
    return Broker(router=Router(MatcherConfig(**kw), node="n1"), node="n1")


def _settle(router, merges=None):
    """Wait for the background compaction to end (and, when given, for
    the merge count to reach ``merges``)."""
    deadline = time.time() + 20
    while time.time() < deadline:
        if not router._compacting and (
                merges is None or router._delta_merges >= merges):
            return
        time.sleep(0.005)
    raise AssertionError("the compaction did not end")


def _state(b):
    auto, id_map, epoch = b.router.automaton()
    return b.helper.state(epoch, id_map), epoch, id_map


def test_the_map_says_which_ids_were_set_in_place():
    r = Router(MatcherConfig(device_min_filters=0, delta_max_filters=4),
               node="n1")
    for i in range(6):
        r.add_route(f"a/{i}")
    r.match_filters(["a/0"])
    m = r._auto_map
    assert isinstance(m, IdMap) and m.reused == [] and len(m) == 6
    r.delete_route("a/1")            # id 1 freed: quarantined
    r.add_route("b/0")               # appended, not reused
    assert m.reused == [] and len(m) == 7 and m[1] is None
    for i in range(1, 5):
        r.add_route(f"b/{i}")        # the bound: a merge
    _settle(r, merges=1)
    m2 = r._auto_map
    assert m2 is not m and m2.reused == []
    fid = r.add_route("c/0")         # the freed id comes back, in place
    assert fid == 1 and m2[1] == "c/0" and m2.reused == [1]
    assert len(m2) == len(r._id_to_filter)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6, 7, 8])
def test_carried_tables_equal_tables_built_from_scratch(seed):
    rng = random.Random(seed)
    b = _broker(delta_max_filters=24, fanout_threshold=6)
    fm, r = b.helper, b.router
    subs = [_Sub() for _ in range(40)]
    held = {s: set() for s in subs}
    for i in range(300):
        b.subscribe(subs[0], f"res/{i}/#")
        held[subs[0]].add(f"res/{i}/#")
    st, epoch, id_map = _state(b)
    _same(fm, st, epoch, id_map)
    assert (fm.rebuilds, fm.carries) == (1, 0)
    merges = n_same = 0
    for step in range(900):
        s = subs[rng.randrange(1, len(subs))]
        roll = rng.random()
        if roll < 0.55:
            f = f"cmd/s{rng.randrange(8)}/d{rng.randrange(400)}/#" \
                if rng.random() < 0.8 else f"hot/{rng.randrange(6)}"
            b.subscribe(s, f)
            held[s].add(f)
        elif roll < 0.9 and held[s]:
            f = rng.choice(sorted(held[s]))
            b.unsubscribe(s, f)
            held[s].discard(f)
        else:
            b.subscriber_down(s)
            held[s].clear()
        if step % 3 == 0:
            # sometimes mid-flatten, sometimes after the swap
            if rng.random() < 0.5:
                _settle(r)
            st, epoch, id_map = _state(b)
            if st is not None and st.bm is None and fm._mirror is not None:
                _same(fm, st, epoch, id_map)
                n_same += 1
            merges = r._delta_merges
    _settle(r)
    st, epoch, id_map = _state(b)
    _same(fm, st, epoch, id_map)
    assert merges >= 5 and n_same > 100
    assert fm.carries >= 5
    # ids came back at merges and were taken again in place
    assert len(r._id_to_filter) < 300 + r._delta_filters
    # what the device would deliver is what the host holds
    for f, row in fm.rows.items():
        fid = r.filter_id(f)
        if fid is not None and fid not in st.big_fids:
            assert sorted(_rows(st.fan, len(id_map))[fid]) == sorted(row)


def test_a_merge_visits_no_unchanged_row():
    b = _broker(delta_max_filters=16)
    fm, r = b.helper, b.router
    keep, s = _Sub(), _Sub()
    for i in range(5000):
        b.subscribe(keep, f"res/{i}/+")
    _state(b)
    assert (fm.rebuilds, fm.patches, fm.carries) == (1, 0, 0)
    looked = []
    real = dict.get

    class _Rows(dict):
        def get(self, key, default=None):
            looked.append(key)
            return real(self, key, default)

    fm.rows = _Rows(fm.rows)
    for i in range(16):
        b.subscribe(s, f"cmd/s0/d{i}/#")       # the bound: a merge
    _settle(r, merges=1)
    for i in range(4):
        b.unsubscribe(s, f"cmd/s0/d{i}/#")     # tombstones now
    looked.clear()
    st, epoch, id_map = _state(b)
    assert epoch == 2 and st.epoch == 2
    assert (fm.rebuilds, fm.carries) == (1, 1)
    # the sync after the merge looked up the 16 + 4 changed rows'
    # filters (a dropped one is not even looked up) and no other
    assert len(looked) <= 20 and fm.rows_patched <= 20
    assert all(f.startswith("cmd/") for f in looked)
    _same(fm, st, epoch, id_map)
    # a second merge, with ids that came back and were taken again
    for i in range(16, 36):
        b.subscribe(s, f"cmd/s0/d{i}/#")
    _settle(r, merges=2)
    looked.clear()
    st, epoch, id_map = _state(b)
    assert fm.rebuilds == 1 and fm.carries == 2 and len(looked) <= 20
    _same(fm, st, epoch, id_map)


def test_tables_of_another_epoch_are_built_whole():
    """An epoch that did not come by a swap (an inline rebuild) builds
    whole, as ever; and a hand-over for an epoch the manager does not
    hold changes nothing."""
    fm = FanoutManager(use_device=False)
    s = _Sub()
    m1 = IdMap(["a", "b"])
    fm.subscribe("a", s)
    st = fm.state(1, m1)
    fm.carry(7, m1, 8, IdMap(m1))    # not its epoch
    assert fm._state is st and fm.carries == 0
    m2 = IdMap(["a", "b"])
    assert fm.state(2, m2).epoch == 2 and fm.rebuilds == 2
    m3 = IdMap(m2)
    fm.carry(2, m2, 3, m3)
    m3[1] = None
    m3.append("c")
    fm.subscribe("c", s)
    # a batch matched just before the swap asks with the old pair: it
    # is served from the one table, brought up to date, not rebuilt
    st_old = fm.state(2, m2)
    assert st_old.epoch == 3 and fm.rebuilds == 2
    st3 = fm.state(3, m3)
    assert st3 is st_old
    assert (fm.rebuilds, fm.carries, fm.patches) == (2, 1, 1)
    assert _rows(st3.fan, 3) == [(0,), (), (0,)]
    assert np.array_equal(np.asarray(st3.fan.row_pairs), fm._mirror[0])
