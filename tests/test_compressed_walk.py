"""Path compression and the compressed walk (ISSUE 16).

Three contracts pinned here:

  1. **Walk-vs-oracle parity on both table layouts** — the lax walk
     returns exactly the host ``TrieOracle``'s filters on narrow and
     wide (chain-fused) tables, packed and raw emit slots.
  2. **Native-vs-numpy compression parity** — the C++ ``csr_compress``
     chain fuser must reproduce ``csr.compress_automaton`` exactly
     (same edges, same renumbering, same hop bounds, same wt).
  3. **Compressed-walk property suite** — randomized topic/filter
     fuzz (``+``/``#``/``$share``, deep literal spines, single-char
     and empty levels) against the host ``TrieOracle`` across
     add/delete churn, delta flatten, devloss rebuild and checkpoint
     round-trip.
"""

import random

import numpy as np
import pytest

from emqx_tpu.oracle import TrieOracle
from emqx_tpu.router import MatcherConfig, Router
from tests.test_match_parity import _check_parity


def _rand_word(rng):
    return rng.choice(["a", "b", "c", "sensor", "x", "y1", "q", ""])


def _rand_filters(rng, n, deep=True):
    out = set()
    while len(out) < n:
        r = rng.random()
        if r < 0.1:
            out.add("$share/g/%s/%s" % (_rand_word(rng),
                                        _rand_word(rng)))
            continue
        if deep and r < 0.35:
            # deep literal spine, sometimes '#'-capped
            depth = rng.randint(8, 16)
            ws = ["s%d" % rng.randint(0, 2) for _ in range(depth)]
            if rng.random() < 0.4:
                ws[-1] = "#"
            out.add("/".join(ws))
            continue
        depth = rng.randint(1, 6)
        ws = []
        for i in range(depth):
            rr = rng.random()
            if rr < 0.2:
                ws.append("+")
            elif rr < 0.28 and i == depth - 1:
                ws.append("#")
            else:
                ws.append(_rand_word(rng))
        out.add("/".join(ws))
    return sorted(out)


def _rand_topics(rng, n, L=16):
    out = []
    for _ in range(n):
        if rng.random() < 0.4:
            depth = rng.randint(8, L)
            out.append("/".join("s%d" % rng.randint(0, 2)
                                for _ in range(depth)))
        else:
            out.append("/".join(_rand_word(rng)
                                for _ in range(rng.randint(1, 6))))
    return out


# -- 1. walk vs oracle on both layouts --------------------------------------


@pytest.mark.parametrize("mode", ["narrow", "wide"])
@pytest.mark.parametrize("pack_ids", [True, False])
def test_walk_oracle_parity(mode, pack_ids):
    rng = random.Random(20160 + pack_ids)
    filters = _rand_filters(rng, 150)
    topics = _rand_topics(rng, 32)
    ovf = _check_parity(filters, topics, L=16, k=16, m=64, mode=mode,
                        pack_ids=pack_ids)
    assert not ovf.any()


# -- 2. native chain-fuser parity ------------------------------------------


def test_native_compress_parity():
    native = pytest.importorskip("emqx_tpu.ops.native")
    if not native.available():
        pytest.skip("native library unavailable")
    rng = random.Random(31)
    eng = native.NativeEngine()
    filters = _rand_filters(rng, 250)
    for i, f in enumerate(filters):
        eng.insert(f, i)
    got = eng.flatten()
    v1 = eng.flatten(skip_hash=True)
    # the native path must have taken the C++ fuser (deep spines ⇒
    # wide mode), and its output must be byte-identical to numpy
    assert got.wt_take > 1
    from emqx_tpu.ops.csr import finalize_automaton
    want = finalize_automaton(v1)
    for field in want._fields:
        a, b = getattr(got, field), getattr(want, field)
        if a is None or isinstance(a, (int, np.integer)):
            assert a == b, field
        else:
            a, b = np.asarray(a), np.asarray(b)
            assert a.shape == b.shape and a.dtype == b.dtype, field
            np.testing.assert_array_equal(a, b, err_msg=field)


def test_native_compress_narrow_fallback():
    native = pytest.importorskip("emqx_tpu.ops.native")
    if not native.available():
        pytest.skip("native library unavailable")
    eng = native.NativeEngine()
    for i, f in enumerate(["a/b", "a/+", "c"]):  # shallow ⇒ narrow
        eng.insert(f, i)
    auto = eng.flatten()
    assert auto.wt_take == 1
    from emqx_tpu.ops.csr import finalize_automaton
    want = finalize_automaton(eng.flatten(skip_hash=True))
    np.testing.assert_array_equal(np.asarray(auto.wt),
                                  np.asarray(want.wt))


# -- 3. compressed-walk property suite -------------------------------------


def _mk(**kw):
    kw.setdefault("device_min_filters", 0)
    kw.setdefault("min_batch", 8)
    return Router(MatcherConfig(**kw), node="node1")


def _assert_parity(r, oracle, topics, tag=""):
    got = r.match_filters(topics)
    for t, row in zip(topics, got):
        assert sorted(row) == sorted(oracle.match(t)), (tag, t)


@pytest.mark.parametrize("delta,match_cache", [
    (False, False), (True, False), (False, True), (True, True)])
def test_compressed_walk_churn_parity(delta, match_cache):
    """Wide-table walk parity vs the oracle across add/delete churn,
    delta-on/off × cache-on/off — the tables stay in wide
    (chain-fused) mode throughout because of the deep spines."""
    rng = random.Random(777)
    r = _mk(delta=delta, match_cache=match_cache,
            delta_max_filters=10_000)
    oracle = TrieOracle()
    live = {}
    for f in _rand_filters(rng, 80):
        r.add_route(f)
        oracle.insert(f)
        live[f] = True
    probe = _rand_topics(rng, 10) + ["$share/g/a/b", "//", "s0"]
    _assert_parity(r, oracle, probe, "warm")
    assert r.walk_info()["mode"] == "wide"
    assert r.walk_info()["chains"] > 0
    for step in range(60):
        if live and rng.random() < 0.45:
            f = rng.choice(sorted(live))
            r.delete_route(f)
            oracle.delete(f)
            del live[f]
        else:
            f = _rand_filters(rng, 1)[0]
            if f not in live:
                r.add_route(f)
                oracle.insert(f)
                live[f] = True
        if step % 12 == 0:
            _assert_parity(r, oracle, probe, f"churn@{step}")
    r.rebuild()
    _assert_parity(r, oracle, probe, "post-rebuild")


def test_compressed_walk_devloss_and_checkpoint(tmp_path):
    """Wide tables must survive the PR 14 lifecycle: devloss rebuild
    re-fuses chains on the fresh backend, checkpoint round-trip
    restores the compressed layout bit-compatibly."""
    from emqx_tpu import checkpoint

    rng = random.Random(99)
    r = _mk(match_cache=False)
    oracle = TrieOracle()
    for f in _rand_filters(rng, 60):
        r.add_route(f)
        oracle.insert(f)
    probe = _rand_topics(rng, 8)
    _assert_parity(r, oracle, probe, "pre")
    assert r.walk_info()["mode"] == "wide"
    # devloss: suspend (host fallback must stay exact) then rebuild
    r.suspend_device()
    _assert_parity(r, oracle, probe, "suspended")
    r.rebuild_device_state()
    _assert_parity(r, oracle, probe, "post-devloss")
    assert r.walk_info()["mode"] == "wide"
    # checkpoint round-trip into a fresh router
    path = str(tmp_path / "walk.npz")
    checkpoint.save(r, path)
    r2 = _mk(match_cache=False)
    checkpoint.load(r2, path)
    _assert_parity(r2, oracle, probe, "restored")
    assert r2.walk_info()["mode"] == "wide"


def test_rewarm_plan_covers_deep_buckets():
    """Devloss rewarm must replay every observed level-bucket shape
    (each is its own compile family): a router that served 16-level
    traffic gets a 16-level warm spine per bucket (ISSUE 16). Since
    the router lists its own dispatch shapes the rewarm's batches are
    that list's (``Router.dispatch_shapes`` → ``warm_batches``)."""
    from emqx_tpu.ops.warmup import warm_batches

    r = _mk()
    for f in ["a/b", "/".join(["s0"] * 16)]:
        r.add_route(f)
    r.match_filters(["a/b"])
    r.match_filters(["/".join(["s0"] * 16)])
    seen = r.observed_levels()
    assert 16 in seen
    plan = list(warm_batches(r.dispatch_shapes(64), r.cache_slots()))
    # every (bucket, level) pair present; the first fresh topic of a
    # deep batch carries exactly the deep level count (depth_bucket
    # keys the compile on the batch's deepest topic)
    depths = {(r.pad_topics(len(topics)), len(topics[0].split("/")))
              for (hits, _m, _d), topics in plan if not hits}
    for b in (8, 64):
        for lv in seen:
            assert (b, lv) in depths
    # bucket select: the fewest topics that pad to 64
    assert [len(t) for s, t in plan if s == (0, 33, 16)] == [33]
