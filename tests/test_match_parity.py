"""Compiled-matcher parity vs the host oracle (the reference's own
trie SUITE is the oracle for the oracle; this closes the loop for the
device path). Runs on CPU via conftest; identical code path on TPU.
"""

import random

import numpy as np
import pytest

from emqx_tpu import topic as T
from emqx_tpu.oracle import TrieOracle
from emqx_tpu.ops.csr import (attach_walk_tables, build_automaton,
                              compress_automaton)
from emqx_tpu.ops.match import match_batch, walk_params
from emqx_tpu.ops.tokenize import WordTable, encode_batch


def _build(filters, mode=None):
    trie = TrieOracle()
    table = WordTable()
    fids = {}
    for f in filters:
        trie.insert(f)
        fids[f] = len(fids)
        for w in T.words(f):
            table.intern(w)
    if mode is None:
        auto = build_automaton(trie, fids, table)
    else:  # pin the kernel layout (both must hold exact parity)
        raw = build_automaton(trie, fids, table, skip_hash=True)
        auto, edges = compress_automaton(raw, force_mode=mode)
        auto = attach_walk_tables(auto, edges)
    inv = {v: k for k, v in fids.items()}
    return trie, table, auto, inv


def _match_device(auto, table, topics, L=16, k=64, m=128,
                  pack_ids=True):
    ids, n, sysm = encode_batch(table, topics, L)
    res = match_batch(auto, ids, n, sysm, k=k, m=m, pack_ids=pack_ids,
                      **walk_params(auto, ids.shape[1]))
    return res


def _check_parity(filters, topics, L=16, k=64, m=128, mode=None,
                  pack_ids=True):
    trie, table, auto, inv = _build(filters, mode=mode)
    res = _match_device(auto, table, topics, L=L, k=k, m=m,
                        pack_ids=pack_ids)
    ids = np.asarray(res.ids)
    cnt = np.asarray(res.count)
    ovf = np.asarray(res.overflow)
    for i, t in enumerate(topics):
        expect = sorted(trie.match(t))
        if ovf[i]:
            # overflow is allowed but must be flagged; host fallback
            got = sorted(trie.match(t))
            assert got == expect
            continue
        got = sorted(inv[j] for j in ids[i] if j >= 0)
        assert len(got) == cnt[i], (t, got, cnt[i])
        assert got == expect, (t, got, expect)
    return ovf


def test_trie_suite_cases():
    filters = ["sensor/1/metric/2", "sensor/+/#", "sensor/#"]
    trie, table, auto, inv = _build(filters)
    res = _match_device(auto, table, ["sensor/1"])
    got = sorted(inv[j] for j in np.asarray(res.ids)[0] if j >= 0)
    assert got == sorted(["sensor/+/#", "sensor/#"])


def test_root_wildcards_and_sys():
    filters = ["#", "+/#", "+/+/#", "$SYS/#", "$SYS/broker/+"]
    _check_parity(filters, [
        "a/b/c", "$SYS/broker/zenmq", "$SYS/broker", "a", "$other/x",
        "$SYS", "x/y", "/", "//",
    ])


def test_hash_matches_parent_level():
    filters = ["sensor", "sensor/#", "a/b/#", "a/b"]
    _check_parity(filters, ["sensor", "sensor/1", "a/b", "a/b/c", "a"])


def test_empty_levels_and_unknown_words():
    filters = ["/+", "+//#", "a//b", "//"]
    _check_parity(filters, ["/x", "/", "a//b", "//", "never/seen/words"])


def test_deep_topics_too_long_flagged():
    filters = ["a/#"]
    trie, table, auto, inv = _build(filters)
    deep = "/".join(["a"] + ["x"] * 40)
    res = _match_device(auto, table, [deep], L=16)
    assert bool(np.asarray(res.overflow)[0])
    assert np.asarray(res.count)[0] == 0


def test_match_after_delete_rebuild():
    trie, table, auto, inv = _build(["a/+", "a/b", "b/#"])
    trie.delete("a/b")
    fids = {"a/+": 0, "b/#": 2}
    auto2 = build_automaton(trie, fids, table)
    res = match_batch(auto2, *encode_batch(table, ["a/b"], 16), k=16,
                      m=16, **walk_params(auto2, 16))
    got = [j for j in np.asarray(res.ids)[0] if j >= 0]
    assert got == [0]


def _random_word(rng):
    return rng.choice(["a", "b", "c", "d", "e", "x", "yy", "z0", "$s", ""])


def _random_filter(rng, maxlen=6):
    n = rng.randint(1, maxlen)
    ws = []
    for i in range(n):
        r = rng.random()
        if r < 0.2:
            ws.append("+")
        elif r < 0.3 and i == n - 1:
            ws.append("#")
        else:
            ws.append(_random_word(rng))
    return "/".join(ws)


@pytest.mark.parametrize("mode", [None, "narrow", "wide"])
def test_random_parity(mode):
    rng = random.Random(123)
    filters = list({_random_filter(rng) for _ in range(400)})
    topics = list({
        "/".join(_random_word(rng) for _ in range(rng.randint(1, 7)))
        for _ in range(300)
    })
    ovf = _check_parity(filters, topics, L=8, k=128, m=256, mode=mode)
    # with K=128 on a 400-filter trie nothing should overflow
    assert not ovf.any()


@pytest.mark.parametrize("mode", ["narrow", "wide"])
def test_deep_chain_parity(mode):
    """Long single-child literal chains — the hash_1m_deep shape the
    compression pass exists for (reference cost model:
    src/emqx_trie.erl:161-186). Both kernel layouts must agree with
    the oracle exactly, including topics that end mid-chain."""
    rng = random.Random(77)
    vocab = [f"v{i}" for i in range(9)]
    filters = set()
    while len(filters) < 300:
        depth = rng.randint(1, 16)
        ws = [rng.choice(vocab) for _ in range(depth)]
        filters.add("/".join(ws[: rng.randint(1, depth)] + ["#"]))
    filters = sorted(filters)
    topics = ["/".join(rng.choice(vocab)
                       for _ in range(rng.randint(1, 16)))
              for _ in range(500)]
    ovf = _check_parity(filters, topics, L=16, k=4, m=128, mode=mode)
    assert not ovf.any()  # no '+' edges: active set is 1 lane


def _dense_plus_filters():
    rng = random.Random(5)
    return list({_random_filter(rng, maxlen=4) for _ in range(200)})


@pytest.mark.parametrize("filters,topics,kw,flags", [
    # k=2 forces active-set overflow on wide NFA frontiers; which
    # rows overflow is the kernel's business, flagged is the contract
    pytest.param(_dense_plus_filters(), ["a/b/c", "a/a/a", "x/yy/z0"],
                 dict(L=8, k=2, m=256), None, id="dense_plus"),
    # at k=2, m=8 the root wildcards stay masked for a $SYS topic, and
    # the one row flagged is the topic past max_levels — flagged, not
    # truncated and matched
    pytest.param(["#", "+/#", "$SYS/#", "a/+/c", "a/b/c", "a/b/#"],
                 ["a/b/c", "$SYS/broker", "a/x/c", "q",
                  "/".join(["d"] * 40)],
                 dict(L=16, k=2, m=8, mode="narrow"),
                 [False, False, False, False, True],
                 id="sys_and_too_deep"),
])
def test_overflow_flagged_not_silent(filters, topics, kw, flags):
    """With a tiny K an overflow must be flagged, never silent: every
    unflagged row equals the oracle's answer."""
    ovf = _check_parity(filters, topics, **kw)
    if flags is not None:
        assert ovf.tolist() == flags


def test_large_scale_smoke():
    rng = random.Random(9)
    filters = list({
        "/".join(rng.choice("abcdefgh") + str(rng.randint(0, 50))
                 for _ in range(rng.randint(2, 5)))
        for _ in range(5000)
    })
    # add some wildcards
    filters += ["a1/+/c2/#", "+/b3/#", "#"]
    topics = ["a1/b3/c2/d4", "a5/b3/x", "nope/nope"]
    _check_parity(filters, topics, L=8, k=64, m=256)
