"""Headline benchmark: publish→match→fan-out throughput on TPU.

Reproduces BASELINE.json config 2/3 (wildcard subscriptions over a
5-level topic tree, Zipf publish mix): builds a subscription trie of
``BENCH_SUBS`` filters (60% literal / 25% single-level ``+`` / 15%
multi-level ``#``), compiles the CSR automaton + fan-out tables to the
device, and measures steady-state matched publishes/sec through the
jitted NFA-walk + subscriber-gather pipeline.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "msgs/sec", "vs_baseline": N}

vs_baseline is measured against the north-star target of 1M publishes/
sec (BASELINE.md — the reference publishes no measured numbers, so the
target is the baseline).
"""

import json
import os
import random
import time

import numpy as np


def _jax():
    """The one backend gate of every mode: import JAX, switch the
    persistent compile cache on, and REQUIRE a TPU. A bench mode run
    without a chip exits non-zero here and prints nothing — a timing
    from XLA's CPU backend is never written under a device metric's
    name (ROADMAP aim 1; the CPU is where tests/ run, the chip is
    where this file runs: ``chiprun -- python bench.py``)."""
    import jax

    from emqx_tpu.profiling import enable_compile_cache
    enable_compile_cache()
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"bench.py: no TPU (platform={d0.platform!r}); refusing "
            f"to run — nothing was measured")
    return jax



def _first_leaf(out):
    import jax as _jax

    return _jax.tree_util.tree_leaves(out)[0]


#: the headline metric shared by the configs aggregate and solo mixed
#: mode
_MATRIX_METRIC = "publish_match_fanout_throughput"

#: aggregate fields lifted from the headline config row
_HEADLINE_FIELDS = ("value", "vs_baseline", "p50_batch_ms",
                    "p99_batch_ms")



#: row provenance (ISSUE 7 satellite): every emitted row carries the
#: matcher configuration that produced it — `walk_mode`
#: (narrow/wide compressed walk), the settled active-set `k`
#: (configured + learned boosts at emit time), the trie `builder`
#: (native C++ vs python), and whether the `delta` automaton was
#: live. Stale staged rows become *detectable* (e.g. a pre-
#: compressed-walk `hash_1m_deep` row shows walk_mode narrow where
#: the current tree would stamp wide) instead of silently riding
#: along. Modes call `_set_prov(router)` once their router settles.
_PROV: dict = {}


def _set_prov(router) -> None:
    global _PROV
    try:
        slots = router._walk_meta.get("slots", 2)
        _PROV = {
            "walk_mode": "wide" if slots == 4 else "narrow",
            "settled_k": int(router.effective_k()),
            "builder": ("native" if router._native is not None
                        else "python"),
            "delta": bool(router.config.delta
                          and router.config.mesh is None),
        }
    except Exception:
        _PROV = {}


def _emit(rec: dict) -> None:
    """Print the mode's JSON line, stamped with the device it ran on
    as JAX reports it (every mode passed the :func:`_jax` gate, so
    the platform is a TPU)."""
    import jax

    for k, v in _PROV.items():
        rec.setdefault(k, v)
    devs = jax.devices()
    rec["platform"] = devs[0].platform
    rec["device_kind"] = devs[0].device_kind
    rec["device_count"] = len(devs)
    print(json.dumps(rec), flush=True)



def _latency_pass(step, batches, iters: int = 20):
    """p50/p99 per-batch latency (ms): run ``step`` synchronously,
    each sample ending in a device→host transfer of the output (the
    completion barrier the product's own fetch uses)."""
    lat = []
    for i in range(iters):
        t = time.perf_counter()
        np.asarray(_first_leaf(step(*batches[i % len(batches)])))
        lat.append((time.perf_counter() - t) * 1000.0)
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 99)))


def _throughput_windows(step, batches, windows, iters):
    """Median window throughput in batches/sec: each window
    dispatches ``iters`` steps and ends with a readback of the LAST
    output — a data dependency that forces every dispatched step to
    complete inside the timed window. One warm-up readback happens
    before timing."""
    np.asarray(_first_leaf(step(*batches[0])))  # absorb first-read cost
    rates = []
    outs = None
    for _ in range(windows):
        t0 = time.perf_counter()
        outs = [step(*batches[i % len(batches)]) for i in range(iters)]
        np.asarray(_first_leaf(outs[-1]))
        rates.append(iters / (time.perf_counter() - t0))
    return float(np.median(rates)), rates, outs


from emqx_tpu.utils.batch import dedup_topics  # noqa: E402


def build_filters(rng, n_subs, words_per_level, levels=5, mix="mixed"):
    """Subscription filters per BASELINE config shape: ``mix`` is
    "mixed" (60/25/15 literal/`+`/`#` — configs 2+3 blended),
    "literal" (config 1), "plus" (config 2) or "hash" (config 3)."""
    filters = set()
    vocab = [[f"w{lvl}_{i}" for i in range(words_per_level)]
             for lvl in range(levels)]
    lo = 1 if levels == 1 else 2
    while len(filters) < n_subs:
        depth = rng.randint(lo, levels)
        ws = [rng.choice(vocab[i]) for i in range(depth)]
        if mix == "mixed":
            r = rng.random()
            if r < 0.25:  # single-level '+'
                ws[rng.randrange(depth)] = "+"
            elif r < 0.40:  # multi-level '#'
                ws = ws[: rng.randint(1, depth)] + ["#"]
        elif mix == "plus":
            ws[rng.randrange(depth)] = "+"
        elif mix == "hash":
            ws = ws[: rng.randint(1, depth)] + ["#"]
        elif mix != "literal":
            raise ValueError(f"unknown filter mix {mix!r}")
        filters.add("/".join(ws))
    return list(filters), vocab


#: bump when BUILD SEMANTICS change (build_filters mix ratios,
#: zipf_choice shape, dedup, encode levels, depth_bucket) — the cache
#: key only sees shapes, so an unbumped semantic change would silently
#: replay the previous round's workload under the new label
_BUILD_REV = 1


def _build_cache_dir():
    """Cache root (BENCH_BUILD_CACHE=0 disables, =<dir> relocates).
    Footprint warning: the full matrix is ~2.7GB (the 10M row alone
    >1GB) — point this at real disk, not a RAM-backed tmpfs."""
    d = os.environ.get("BENCH_BUILD_CACHE", "/tmp/emqx_bench_cache")
    return None if d == "0" else d


def _build_cache_load(key: str):
    """Host-array build cache: the big-subs builds (filters, trie
    insert, flatten, batch encode) cost minutes of pure-host work
    that is IDENTICAL run to run (seeded rng). Caching the device
    inputs makes a TPU-recovery matrix far more likely to fit its
    row budget. Returns the array dict or None. Opt-out:
    BENCH_BUILD_CACHE=0 (=<dir> relocates)."""
    d = _build_cache_dir()
    if d is None:
        return None
    try:
        return dict(np.load(os.path.join(d, key + ".npz"),
                            allow_pickle=False))
    except Exception:
        return None


def _build_cache_save(key: str, arrs: dict) -> None:
    d = _build_cache_dir()
    if d is None:
        return
    tmp = None
    try:
        os.makedirs(d, exist_ok=True)
        # pid-unique tmp: a prewarm and a recovery bench may build
        # the same key concurrently; sharing one tmp name would let
        # them corrupt each other's half-written file
        tmp = os.path.join(d, f"{key}.{os.getpid()}.tmp.npz")
        np.savez(tmp, **arrs)
        os.replace(tmp, os.path.join(d, key + ".npz"))
    except Exception:
        # cache is best-effort — but a half-written tmp must not
        # squat multi-hundred-MB of the cache volume (ENOSPC is
        # self-reinforcing otherwise)
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def build_main_inputs(n_subs: int, batch: int, levels: int, mix: str,
                      traffic: str, wpl: int, n_batches: int = 8):
    """The main-mode host build — filters, automaton, fan table and
    8 encoded publish batches — through the array cache (a pure
    function of the seeded rng, so a cache hit is exact). JAX-free.
    Returns
    ``(use_native, cached, auto, fan, host_batches, uniques,
    n_filters, topic_lists)`` — ``topic_lists`` is each batch's
    unique-topic strings (the match-cache rows key on them; artifacts
    written before the field existed miss on load and rebuild)."""
    import random as _random

    from emqx_tpu.ops import native
    from emqx_tpu.ops.csr import Automaton
    from emqx_tpu.ops.fanout import FanoutTable, build_fanout
    from emqx_tpu.ops.match import depth_bucket

    use_native = native.available()
    # key carries a schema version + which engine built the arrays:
    # a field added next round or a native/python provenance mix must
    # miss, not crash or mislabel the measurement. The cache stores
    # only the CSR flatten artifact (v1 fields) — the v2 walk tables
    # (compression, hashing) are a deterministic post-pass re-derived
    # on load, so a kernel-layout change never invalidates the
    # minutes-long host build.
    cache_key = (f"mixed_v2r{_BUILD_REV}"
                 f"_{'nat' if use_native else 'py'}"
                 f"_s{n_subs}_b{batch}_l{levels}_{mix}_{traffic}"
                 f"_w{wpl}_n{n_batches}")
    _V1_FIELDS = ("row_ptr", "edge_word", "edge_child", "plus_child",
                  "hash_filter", "end_filter", "n_states", "n_edges")
    cached = _build_cache_load(cache_key)
    if cached is not None:
        try:
            from emqx_tpu.ops.csr import finalize_automaton
            auto = Automaton(**{
                f: (cached[f"a_{f}"] if f"a_{f}" in cached
                    else int(cached[f"s_{f}"]))
                for f in _V1_FIELDS})
            auto = finalize_automaton(auto)
            fan = FanoutTable(**{
                f: (cached[f"f_{f}"] if f"f_{f}" in cached
                    else (int(cached[f"fs_{f}"]) if f"fs_{f}" in cached
                          else None))
                for f in FanoutTable._fields})
            host_batches = [
                (cached[f"b{i}_ids"], cached[f"b{i}_n"],
                 cached[f"b{i}_sysm"].astype(bool))
                for i in range(n_batches)]
            topic_lists = [cached[f"b{i}_topics"].tolist()
                           for i in range(n_batches)]
            uniques = [int(u) for u in cached["uniques"]]
            n_filters = int(cached["n_filters"])
            return (use_native, True, auto, fan, host_batches,
                    uniques, n_filters, topic_lists)
        except Exception:
            pass  # schema-drifted file: fall through to a rebuild

    rng = _random.Random(0)
    filters, vocab = build_filters(rng, n_subs, words_per_level=wpl,
                                   levels=levels, mix=mix)
    if use_native:
        eng = native.NativeEngine()
        for i, f in enumerate(filters):
            eng.insert(f, i)
        auto = eng.flatten()
        encode = eng.encode_batch
    else:
        insert, flatten, encode = _python_engine()
        for i, f in enumerate(filters):
            insert(f, i)
        auto = flatten()
    # one subscriber per subscription (10M-sub scale is sub-id
    # bitmaps over the same CSR; bench config keeps 1:1)
    fan = build_fanout({i: [i] for i in range(len(filters))},
                       len(filters))
    n_filters = len(filters)

    # publish batches: `batch` LOGICAL messages each, Zipf over the
    # filter tree's own vocabulary, deduplicated to unique topics
    # before the device (the product ingress does the same per tick —
    # hot topics collapse; throughput counts logical messages, and
    # per-unique rates are reported alongside)
    host_batches = []
    uniques = []
    topic_lists = []
    lo = 1 if levels == 1 else 2
    pick = (zipf_choice if traffic == "zipf"
            else lambda r, items: r.choice(items))
    for _ in range(n_batches):
        topics = [
            "/".join(pick(rng, vocab[i])
                     for i in range(rng.randint(lo, levels)))
            for _ in range(batch)
        ]
        uniq, _inv = dedup_topics(topics)
        uniques.append(len(uniq))
        topic_lists.append(uniq)
        ids_, n_, sysm_ = encode(uniq, 16)
        ids_, n_ = depth_bucket(ids_, n_)
        host_batches.append((ids_, n_, sysm_))
    arrs = {"uniques": np.asarray(uniques, np.int64),
            "n_filters": np.int64(n_filters)}
    for f, v in zip(Automaton._fields, auto):
        if f not in _V1_FIELDS:
            continue  # walk tables re-derive from the flatten on load
        arrs[f"a_{f}" if isinstance(v, np.ndarray) else f"s_{f}"] = v
    for f, v in zip(FanoutTable._fields, fan):
        if isinstance(v, np.ndarray):
            arrs[f"f_{f}"] = v
        elif v is not None:
            arrs[f"fs_{f}"] = np.int64(v)
    for i, (ids_, n_, sysm_) in enumerate(host_batches):
        arrs[f"b{i}_ids"] = ids_
        arrs[f"b{i}_n"] = n_
        arrs[f"b{i}_sysm"] = sysm_
        # unicode array, not object dtype: the cache loads with
        # allow_pickle=False
        arrs[f"b{i}_topics"] = np.asarray(topic_lists[i])
    _build_cache_save(cache_key, arrs)
    return (use_native, False, auto, fan, host_batches, uniques,
            n_filters, topic_lists)


def _python_engine():
    """(insert, flatten, encode) on the pure-Python builder — the
    toolchain-less fallback shared by main() and shared()."""
    from emqx_tpu.oracle import TrieOracle
    from emqx_tpu.ops.csr import build_automaton
    from emqx_tpu.ops.tokenize import WordTable
    from emqx_tpu.ops.tokenize import encode_batch as _eb

    trie, table, fids = TrieOracle(), WordTable(), {}

    def insert(f, i):
        trie.insert(f)
        fids[f] = i
        for w in f.split("/"):
            table.intern(w)

    def flatten():
        return build_automaton(trie, fids, table)

    def encode(topics, max_levels):
        return _eb(table, topics, max_levels)

    return insert, flatten, encode


def zipf_choice(rng, items, a=1.3):
    # Zipf-ish publish mix (BASELINE config 2)
    n = len(items)
    while True:
        k = int(rng.paretovariate(a)) - 1
        if k < n:
            return items[k]


def bigfan():
    """BENCH_MODE=bigfan — the >1024-subscriber sharded-topic regime
    (BASELINE config 5 scale): huge per-filter subscriber sets stored
    as bitmap rows; fan-out = Pallas OR-streaming kernel
    (emqx_tpu.ops.bitmap). Reports effective deliveries/sec."""
    import time as _t

    jax = _jax()
    import jax.numpy as jnp

    from emqx_tpu.ops.bitmap import or_bitmaps_dma, words_for

    n_subs = int(os.environ.get("BENCH_SUBS", "10000000"))
    n_big = int(os.environ.get("BENCH_BIG", "64"))
    B = int(os.environ.get("BENCH_BATCH", "256"))
    mb = int(os.environ.get("BENCH_MB", "8"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "5")))
    density = float(os.environ.get("BENCH_DENSITY", "0.05"))

    rng = np.random.default_rng(0)
    W = words_for(n_subs)
    # random member masks at the target density (building 64 x 10M-bit
    # rows via explicit id lists would just bench numpy). Only real
    # subscriber positions < n_subs get bits — the pow2 pad region
    # stays zero, exactly as build_bitmaps leaves it — and rows are
    # generated one at a time in float32 to bound host RAM
    bitmaps = np.zeros((n_big, W), dtype=np.uint32)
    for r in range(n_big):
        bits = (rng.random(n_subs, dtype=np.float32) < density)
        packed = np.packbits(bits, bitorder="little")
        packed = np.pad(packed, (0, W * 4 - packed.size))
        bitmaps[r] = packed.view(np.uint32)
    rows = np.full((B, mb), -1, np.int32)
    for b in range(B):
        k = rng.integers(1, mb + 1)
        rows[b, :k] = rng.choice(n_big, size=k, replace=False)
    bm = jax.device_put(bitmaps)
    rows_d = jax.device_put(rows)

    # the timed step reduces to per-topic counts on device: holding
    # iters x [B, W] fan-out bitmaps in the async queue exhausts HBM
    # at 10M subs (2 MB per topic row). Per-topic popcounts fit int32
    # (<= W*32 bits < 2^31); the batch total sums on the host in
    # int64 — jnp int64 would be silently demoted without x64
    or_fn = or_bitmaps_dma  # the kernel the product dispatches
    step = jax.jit(lambda b_, r_: jnp.sum(
        jax.lax.population_count(or_fn(b_, r_)),
        axis=1, dtype=jnp.int32))
    jax.block_until_ready(step(bm, rows_d))  # compile
    batches_per_s, rates, outs = _throughput_windows(
        step, [(bm, rows_d)], windows, iters)
    deliveries_per_batch = int(
        np.asarray(outs[-1]).astype(np.int64).sum())
    deliveries_per_s = batches_per_s * deliveries_per_batch
    p50, p99 = _latency_pass(step, [(bm, rows_d)], iters=10)
    import sys
    print(json.dumps({
        "mode": "bigfan", "subs": n_subs, "big_filters": n_big,
        "batch": B, "deliveries_per_batch": deliveries_per_batch,
        "device": str(jax.devices()[0]),
        "window_batches": [round(r, 1) for r in rates],
    }), file=sys.stderr, flush=True)
    _emit({
        "metric": "bigfan_bitmap_deliveries",
        "value": round(deliveries_per_s, 1),
        "unit": "deliveries/sec",
        # north star counts 1M msgs/s; one delivery >= one matched msg
        "vs_baseline": round(deliveries_per_s / 1_000_000, 3),
        "p50_batch_ms": round(p50, 3),
        "p99_batch_ms": round(p99, 3),
    })


def shared():
    """BENCH_MODE=shared — BASELINE config 4: $share/<group>
    load-balanced dispatch at 1M shared subscribers, in ONE fused
    device step: match over the batch's UNIQUE topics (hot topics
    collapse exactly as the main publish path dedups), a device
    inverse-index gather expands match ids back to per-message rows,
    then the hash-strategy group pick draws per MESSAGE
    (ops.fanout.pick_shared — per-message semantics preserved, the
    reference picks per publish, src/emqx_shared_sub.erl:229-275)."""
    import time as _t

    jax = _jax()
    import jax.numpy as jnp

    from emqx_tpu.ops import native
    from emqx_tpu.ops.csr import device_view
    from emqx_tpu.ops.fanout import build_fanout, pick_shared
    from emqx_tpu.ops.match import depth_bucket, match_batch, walk_params

    n_subs = int(os.environ.get("BENCH_SUBS", "1000000"))
    n_groups = int(os.environ.get("BENCH_GROUPS", "1000"))
    batch = int(os.environ.get("BENCH_BATCH", "65536"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "5")))
    k = int(os.environ.get("BENCH_K", "8"))
    m = int(os.environ.get("BENCH_M", "16"))
    levels = 5

    rng = random.Random(0)
    t0 = time.time()
    # one shared filter per group; members spread evenly (the
    # reference stores {group, topic} -> member rows the same way)
    filters, vocab = build_filters(rng, n_groups, words_per_level=60,
                                   levels=levels)
    if native.available():
        eng = native.NativeEngine()
        insert, flatten, encode = eng.insert, eng.flatten, \
            eng.encode_batch
    else:
        # toolchain-less host: the Python builder (slower build, same
        # device program — the row must not error out of the matrix)
        insert, flatten, encode = _python_engine()
    rows = {}
    per = n_subs // n_groups
    for i, f in enumerate(filters):
        insert(f, i)
        rows[i] = range(i * per, (i + 1) * per)
    host_auto = flatten()
    fan = build_fanout(rows, len(filters))
    build_s = time.time() - t0

    auto = jax.device_put(device_view(host_auto))
    fan = jax.device_put(fan)
    batches = []
    uniques = []
    seed_rng = np.random.default_rng(1)
    for _ in range(8):
        topics = ["/".join(zipf_choice(rng, vocab[i])
                           for i in range(rng.randint(2, levels)))
                  for _ in range(batch)]
        uniq, inv = dedup_topics(topics)
        uniques.append(len(uniq))
        ids_, n_, sysm_ = encode(uniq, 16)
        ids_, n_ = depth_bucket(ids_, n_)
        inv_ = np.asarray(inv, dtype=np.int32)
        seeds = seed_rng.integers(0, 2**31 - 1, size=batch,
                                  dtype=np.int32)
        batches.append(jax.device_put((ids_, n_, sysm_, inv_, seeds)))

    def step(ids, n, sysm, inv, seeds):
        res = match_batch(auto, ids, n, sysm, k=k, m=m,
                          **walk_params(host_auto, ids.shape[1]))
        # unique-topic match ids -> per-message rows: ONE [B, M]
        # gather, then the per-message member draw
        ids_full = res.ids[inv]
        picks = pick_shared(fan, ids_full, seeds)
        return jnp.sum(picks >= 0, dtype=jnp.int32), res.overflow

    for b_ in batches:  # one compile per distinct unique-shape bucket
        jax.block_until_ready(step(*b_))
    batches_per_s, rates_b, outs = _throughput_windows(
        step, batches, windows, iters)
    throughput = batches_per_s * batch
    rates = [r * batch for r in rates_b]
    picked = int(outs[0][0])
    p50, p99 = _latency_pass(step, batches)
    import sys
    print(json.dumps({
        "mode": "shared", "subs": n_subs, "groups": n_groups,
        "batch": batch, "build_s": round(build_s, 1),
        "avg_unique_topics": round(float(np.mean(uniques)), 1),
        "picks_per_batch": picked,
        "device": str(jax.devices()[0]),
        "window_mmsgs": [round(r / 1e6, 2) for r in rates],
    }), file=sys.stderr, flush=True)
    _emit({
        "metric": "shared_dispatch_throughput",
        # the round-5 walk rewrite redefines the device program: a
        # staged pre-rewrite record must not satisfy this mode
        "workload": "walkv2",
        "value": round(throughput, 1),
        "unit": "msgs/sec",
        "vs_baseline": round(throughput / 1_000_000, 3),
        "p50_batch_ms": round(p50, 3),
        "p99_batch_ms": round(p99, 3),
    })


def main():
    n_subs = int(os.environ.get("BENCH_SUBS", "1000000"))
    batch = int(os.environ.get("BENCH_BATCH", "131072"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    # active-set capacity: adaptive like the product (Router.boost_k).
    # Start narrow — gather volume scales with k, and the round-4 A/B
    # measured k=4 at +33% (headline) / +61% (16-level hash, zero
    # overflow) vs the old fixed 8 — then grow once if the warmup
    # shows the product's boost threshold (>1/8 of unique rows
    # match-overflowed: the 10M-sub trie is dense enough to need 8).
    # BENCH_K pins it for A/B.
    k_env = os.environ.get("BENCH_K")
    k = int(k_env) if k_env else 4
    m = int(os.environ.get("BENCH_M", "64"))
    d = int(os.environ.get("BENCH_D", "32"))
    # BASELINE-config shape knobs (the `configs` orchestrator drives
    # these; defaults reproduce the historical blended workload)
    levels = int(os.environ.get("BENCH_LEVELS", "5"))
    mix = os.environ.get("BENCH_MIX", "mixed")
    traffic = os.environ.get("BENCH_TRAFFIC", "zipf")
    wpl = int(os.environ.get("BENCH_WPL", "60"))

    jax = _jax()

    from emqx_tpu.ops.csr import device_view
    from emqx_tpu.ops.fanout import expand_packed
    from emqx_tpu.ops.match import match_batch, walk_params
    from emqx_tpu.ops.pack import budget_for, pack_matches

    t0 = time.time()
    use_native, cached, host_auto, fan, host_batches, uniques, \
        n_filters, topic_lists = build_main_inputs(
            n_subs, batch, levels, mix, traffic, wpl)
    build_s = time.time() - t0

    # the walk's k bound follows the trie's algebra: no '+' edges ⇒
    # the active set is provably ≤1 lane (the adaptive boost below
    # still covers any workload the bound mis-sizes)
    has_plus = bool(
        (np.asarray(host_auto.node2)[:max(host_auto.v2_states, 1), 0]
         >= 0).any())
    if k_env is None and not has_plus:
        k = 1

    # device_put once — the steady-state path matches device-resident
    # arrays produced by the ingress batcher, and re-shipping numpy
    # per step would time the host link, not the kernel. Only the
    # walkable tables ship (the CSR flatten artifact stays on host).
    auto = jax.device_put(device_view(host_auto))
    fan = jax.device_put(fan)
    batches = [jax.device_put(b) for b in host_batches]

    # the PRODUCT pipeline: match → pack → fused sparse expansion
    # (broker.publish_begin runs exactly this); budgets start sized
    # off the batch and then SHRINK to the warmup's observed totals —
    # the broker's learned buckets work the same way (grow on
    # overflow, so steady state runs the fitting bucket). The packed
    # buffers' cummax/gather costs scale with the BUDGET, not the
    # actual traffic, so a worst-case budget taxes every batch.
    bucket_rows = max(b[0].shape[0] for b in batches)
    PM = budget_for(bucket_rows, max(8, k))
    Q = budget_for(bucket_rows, int(os.environ.get("BENCH_PACKQ", "16")))

    # BENCH_CACHE=1 — the product's epoch-guarded publish match
    # cache in front of the walk (ops/match_cache.py): per batch,
    # probe the unique topics, walk ONLY the misses (pack_ids=True —
    # fixed-width rows the cache stores), merge hits from HBM, insert
    # fresh rows. The cache-off rows keep the raw-kernel pipeline
    # above byte-for-byte, so on/off pairs isolate the cache's win.
    use_cache = os.environ.get("BENCH_CACHE") == "1"
    cache = None
    if use_cache:
        from emqx_tpu.ops.match_cache import MatchCache

        cache = MatchCache(
            int(os.environ.get("BENCH_CACHE_SLOTS", str(1 << 18))), m)

    def make_step(k_, pm_, q_):
        def step(ids, n, sysm):
            res = match_batch(auto, ids, n, sysm, k=k_, m=m,
                              pack_ids=False,
                              **walk_params(host_auto, ids.shape[1]))
            m_ptr, packed = pack_matches(res.ids, pm=pm_)
            f_ptr, subs, src, total = expand_packed(fan, m_ptr,
                                                    packed, q=q_)
            return res.count, f_ptr, res.overflow, total, m_ptr[-1]
        return step

    def make_cache_step(k_, pm_, q_):
        import jax.numpy as jnp

        key = ("bench", k_)  # k growth must re-walk negative entries

        def step(i):
            ids_, n_, sysm_ = host_batches[i]
            b_pad = ids_.shape[0]
            probe = cache.probe(topic_lists[i], key)
            miss_rows = miss_ovf = None
            if probe.miss_topics:
                # host slice + pad of the pre-encoded rows — the
                # product encodes only its misses the same way
                rows = np.asarray(probe.miss_pos)
                mb_pad = 8
                while mb_pad < len(rows):
                    mb_pad *= 2
                mi = np.zeros((mb_pad, ids_.shape[1]), ids_.dtype)
                mi[:len(rows)] = ids_[rows]
                mn = np.zeros((mb_pad,), n_.dtype)
                mn[:len(rows)] = n_[rows]
                ms = np.zeros((mb_pad,), bool)
                ms[:len(rows)] = sysm_[rows]
                res = match_batch(
                    auto, mi, mn, ms, k=k_, m=m, pack_ids=True,
                    **walk_params(host_auto, ids_.shape[1]))
                miss_rows, miss_ovf = res.ids, res.overflow
                cache.insert(probe, miss_rows, miss_ovf)
            merged, ovf, _movf = cache.merge(b_pad, probe,
                                             miss_rows, miss_ovf)
            m_ptr, packed = pack_matches(merged, pm=pm_)
            f_ptr, subs, src, total = expand_packed(fan, m_ptr,
                                                    packed, q=q_)
            count = jnp.sum(merged >= 0, axis=1, dtype=jnp.int32)
            return count, f_ptr, ovf, total, m_ptr[-1]
        return step

    make = make_cache_step if use_cache else make_step
    step_batches = [(i,) for i in range(len(batches))] if use_cache \
        else batches
    step = make(k, PM, Q)
    ovf_w = uniq_w = 0
    tot_m = tot_q = 0
    for b_, u in zip(step_batches, uniques):  # one compile per shape
        out = step(*b_)
        jax.block_until_ready(out)
        ovf_w += int(np.asarray(out[2])[:u].sum())
        uniq_w += u
        tot_m = max(tot_m, int(np.asarray(out[4])))
        tot_q = max(tot_q, int(np.asarray(out[3])))
    # first full pass = the cross-batch (cold) repeat rate; steady
    # state below re-visits the same batches and measures hot hits
    warm_hit_rate = cache.stats()["hit_rate"] if use_cache else None
    if k_env is None and ovf_w * 8 > uniq_w:
        # the product's boost_k response to the same >1/8 signal:
        # grow once and re-warm (overflowed rows would otherwise be
        # host-resolved — exact, but not what steady state runs)
        k = k * 2
        step = make(k, PM, Q)
        tot_m = tot_q = 0
        for b_ in step_batches:
            out = step(*b_)
            jax.block_until_ready(out)
            tot_m = max(tot_m, int(np.asarray(out[4])))
            tot_q = max(tot_q, int(np.asarray(out[3])))
    # shrink to fitting buckets (1.3x headroom; overflow accounting
    # below still flags any batch that outgrows them)
    fit_m = budget_for(1, 1, floor=64)
    while fit_m < tot_m * 1.3:
        fit_m *= 2
    fit_q = budget_for(1, 1, floor=64)
    while fit_q < tot_q * 1.3:
        fit_q *= 2
    if fit_m < PM or fit_q < Q:
        PM, Q = min(PM, fit_m), min(Q, fit_q)
        step = make(k, PM, Q)
        for b_ in step_batches:
            jax.block_until_ready(step(*b_))
    if use_cache:
        st0 = cache.stats()  # steady-state hit rate = windows only

    # Time several independent windows and report the median window
    # throughput (the one-chip machine shares its host's cores);
    # every window ends in a readback (see _throughput_windows).
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "5")))
    batches_per_s, rates, outs = _throughput_windows(
        step, step_batches, windows, iters)
    throughput = batches_per_s * batch
    p50, p99 = _latency_pass(step, step_batches)

    # per-stage attribution columns (ISSUE 2; docs/OBSERVABILITY.md):
    # time nested pipeline PREFIXES — match only, match+pack, full —
    # and difference them, attributing the row's latency to a stage
    # instead of a vibe. Two small extra compiles + a few timed
    # iterations; BENCH_BREAKDOWN=0 skips. Cache rows skip it too:
    # their step is host-orchestrated (probe/merge around the walk)
    # and the cache_* info fields already carry that split.
    stage_ms = None
    if not use_cache and os.environ.get("BENCH_BREAKDOWN", "1") == "1":
        def step_match(ids, n, sysm):
            res = match_batch(auto, ids, n, sysm, k=k, m=m,
                              pack_ids=False,
                              **walk_params(host_auto, ids.shape[1]))
            return res.ids

        def step_mp(ids, n, sysm):
            res = match_batch(auto, ids, n, sysm, k=k, m=m,
                              pack_ids=False,
                              **walk_params(host_auto, ids.shape[1]))
            m_ptr, packed = pack_matches(res.ids, pm=PM)
            return packed, m_ptr

        for s_ in (step_match, step_mp):  # compile outside the timing
            for b_ in step_batches:
                jax.block_until_ready(s_(*b_))
        p50_m, _ = _latency_pass(step_match, step_batches, iters=8)
        p50_mp, _ = _latency_pass(step_mp, step_batches, iters=8)
        stage_ms = {
            "match": round(p50_m, 3),
            "pack": round(max(0.0, p50_mp - p50_m), 3),
            "expand": round(max(0.0, p50 - p50_mp), 3),
        }

    # walk-cost columns (ISSUE 16): per-topic hop count under the
    # compressed automaton — the quantity path compression shrinks.
    # hops_for_level[L] is the walk's step bound for an L-level
    # topic; per-topic gathers follow the kernel's own cost model
    # (GATHERS_PER_HOP fetches per hop per active lane).
    from emqx_tpu.ops.walk_pallas import GATHERS_PER_HOP
    hl_ = np.asarray(host_auto.hops_for_level)
    lv_ = np.concatenate([np.asarray(b_[1])[:u]
                          for b_, u in zip(host_batches, uniques)])
    lv_ = lv_[lv_ > 0]
    steps_per_topic = hl_[np.minimum(lv_, len(hl_) - 1)]
    walk_levels_p50 = int(np.percentile(steps_per_topic, 50))
    gathers_per_topic = round(
        float(steps_per_topic.mean()) * GATHERS_PER_HOP * k, 1)

    # compaction A/B (ISSUE 16): re-finalize the SAME flatten with
    # compression forced off, time the match stage on both tables,
    # report the off-p50 and the speedup. Only on rows that ask
    # (deep/uniform — _CONFIG_MATRIX sets BENCH_COMPRESS_AB) and only
    # when the live tables actually compressed (wide mode).
    compress_ab = None
    if (os.environ.get("BENCH_COMPRESS_AB") == "1"
            and not use_cache and int(host_auto.wt_take) > 1):
        from emqx_tpu.ops.csr import finalize_automaton
        off_host = finalize_automaton(host_auto, force_mode="narrow")
        off_dev = jax.device_put(device_view(off_host))

        def step_off(ids, n, sysm):
            res = match_batch(off_dev, ids, n, sysm, k=k, m=m,
                              pack_ids=False,
                              **walk_params(off_host, ids.shape[1]))
            return res.ids

        def step_on(ids, n, sysm):
            res = match_batch(auto, ids, n, sysm, k=k, m=m,
                              pack_ids=False,
                              **walk_params(host_auto, ids.shape[1]))
            return res.ids

        for s_ in (step_off, step_on):  # compile outside the timing
            for b_ in step_batches:
                jax.block_until_ready(s_(*b_))
        off_p50, _ = _latency_pass(step_off, step_batches, iters=8)
        on_p50, _ = _latency_pass(step_on, step_batches, iters=8)
        compress_ab = {
            "compress_off_p50_ms": round(off_p50, 3),
            "compress_speedup": (round(off_p50 / on_p50, 2)
                                 if on_p50 > 0 else None),
        }

    counts = np.asarray(outs[0][0])[:uniques[0]]
    deliv = np.diff(np.asarray(outs[0][1]))[:uniques[0]]
    ovf = sum(int(np.asarray(o[2]).sum()) for o in outs)
    # budget truncation counts as overflow too (silent undercount
    # otherwise): packed matches past PM, deliveries past Q
    ovf += sum(int(np.asarray(o[3]) > Q) for o in outs)
    ovf += sum(int(np.asarray(o[4]) > PM) for o in outs)
    avg_unique = float(np.mean(uniques))
    info = {
        "mix": mix, "traffic": traffic, "levels": levels,
        "subs": n_filters,
        "batch": batch,
        "k": k,  # active-set capacity the run settled on (adaptive)
        "avg_unique_topics": round(avg_unique, 1),
        "native": use_native,
        "build_cached": bool(cached),
        "build_s": round(build_s, 1),
        "avg_matches_per_unique": round(float(counts.mean()), 2),
        "avg_deliveries_per_unique": round(float(deliv.mean()), 2),
        "overflow_frac": round(ovf / (avg_unique * iters), 6),
        "device": str(jax.devices()[0]),
        "unique_kmsgs_per_s": round(batches_per_s * avg_unique / 1e3, 1),
        "window_mmsgs": [round(r * batch / 1e6, 2) for r in rates],
        "walk_levels_p50": walk_levels_p50,
        "gathers_per_topic": gathers_per_topic,
    }
    if stage_ms is not None:
        info["stage_p50_ms"] = stage_ms
    if compress_ab is not None:
        info.update(compress_ab)
    if use_cache:
        st1 = cache.stats()
        probed = (st1["hit"] - st0["hit"]) + (st1["miss"] - st0["miss"])
        info["cache"] = True
        info["cache_slots"] = cache.slots
        info["cache_entries"] = st1["entries"]
        # cold = the first pass over distinct batches (true
        # cross-batch repetition); steady = the timed windows
        info["cache_warm_hit_rate"] = round(warm_hit_rate, 4)
        info["cache_hit_rate"] = round(
            (st1["hit"] - st0["hit"]) / probed, 4) if probed else 0.0
    import sys
    print(json.dumps(info), file=sys.stderr, flush=True)
    # row provenance (mode builds raw automatons, no Router): stamp
    # from the settled walk itself
    global _PROV
    _PROV = {
        "walk_mode": "wide" if host_auto.wt_slots == 4 else "narrow",
        "settled_k": int(k),
        "builder": "native" if use_native else "python",
        "delta": False,  # raw-automaton mode: no route-churn plane
    }
    rec = {
        "metric": "publish_match_fanout_throughput",
        "value": round(throughput, 1),
        "unit": "msgs/sec",
        "vs_baseline": round(throughput / 1_000_000, 3),
        "p50_batch_ms": round(p50, 3),
        "p99_batch_ms": round(p99, 3),
        "walk_levels_p50": walk_levels_p50,
        "gathers_per_topic": gathers_per_topic,
    }
    if stage_ms is not None:
        rec["stage_p50_ms"] = stage_ms
    if compress_ab is not None:
        rec.update(compress_ab)
    _emit(rec)


def live():
    """BENCH_MODE=live — socket-to-deliver over loopback TCP through
    the full broker stack (see emqx_tpu/bench_live.py)."""
    from emqx_tpu.bench_live import live as _live
    _live(emit=_emit)


def deep_smoke():
    """BENCH_MODE=deep_smoke — the path-compression CI gate
    (ISSUE 16, scripts/ci.sh): a 16-level workload must (a) actually
    level-compress — the walk's hop bound strictly below the raw
    level count — and (b) hold exact host-oracle parity through the
    compressed tables and the product fetch seam. Numbers are not
    gated here; the compression + correctness booleans ARE."""
    import random as _random

    n_filters = int(os.environ.get("DEEP_FILTERS", "400"))
    n_topics = int(os.environ.get("DEEP_TOPICS", "256"))
    levels = 16

    jax = _jax()

    from emqx_tpu.oracle import TrieOracle
    from emqx_tpu.ops import native
    from emqx_tpu.ops.csr import device_view
    from emqx_tpu.ops.match import depth_bucket, walk_params
    from emqx_tpu.ops.walk_pallas import (fetch_walk_result,
                                          match_batch_auto)

    rng = _random.Random(6)
    filters = set()
    while len(filters) < n_filters:
        ws = ["w%d" % rng.randint(0, 3) for _ in range(levels)]
        r = rng.random()
        if r < 0.25:
            ws[rng.randint(0, levels - 1)] = "+"
        elif r < 0.4:
            ws = ws[:rng.randint(4, levels - 1)] + ["#"]
        filters.add("/".join(ws))
    filters = sorted(filters)

    oracle = TrieOracle()
    use_native = native.available()
    if use_native:
        eng = native.NativeEngine()
        for i, f in enumerate(filters):
            eng.insert(f, i)
            oracle.insert(f)
        host_auto = eng.flatten()
        encode = eng.encode_batch
    else:
        insert, flatten, encode = _python_engine()
        for i, f in enumerate(filters):
            insert(f, i)
            oracle.insert(f)
        host_auto = flatten()

    hl = np.asarray(host_auto.hops_for_level)
    deep_hops = int(hl[min(levels, len(hl) - 1)])
    # the gate: a 16-level literal-spined trie MUST compress — the
    # walk takes strictly fewer hops than the topic has levels
    assert int(host_auto.wt_take) > 1, \
        "deep workload did not take the wide (compressed) layout"
    assert deep_hops < levels, \
        f"no compression: {deep_hops} hops for {levels} levels"

    topics = ["/".join("w%d" % rng.randint(0, 3)
                       for _ in range(levels))
              for _ in range(n_topics)]
    # seed guaranteed-match probes (wildcard rows above cover misses)
    for f in rng.sample(filters, min(32, len(filters))):
        topics.append("/".join(
            "w0" if w == "+" else w
            for w in f.split("/")).replace("/#", "/w0"))
    ids_, n_, sysm_ = encode(topics, levels)
    ids_, n_ = depth_bucket(ids_, n_)
    auto = jax.device_put(device_view(host_auto))
    t0 = time.time()
    res = match_batch_auto(auto, ids_, n_, sysm_, k=16, m=64,
                           pack_ids=True,
                           **walk_params(host_auto, ids_.shape[1]))
    r_ids, r_cnt, r_ovf = fetch_walk_result(res)
    walk_s = time.time() - t0
    inv = {i: f for i, f in enumerate(filters)}
    mismatch = 0
    for i, t in enumerate(topics):
        want = sorted(oracle.match(t))
        if r_ovf[i]:
            continue  # flagged rows host-resolve in the product
        got = sorted(inv[j] for j in r_ids[i] if j >= 0)
        if got != want:
            mismatch += 1
    assert mismatch == 0, f"{mismatch} topics diverged from oracle"

    _emit({
        "metric": "deep_smoke_parity",
        "value": 1,
        "unit": "ok",
        "filters": len(filters),
        "topics": len(topics),
        "levels": levels,
        "walk_hops_deep": deep_hops,
        "compressed": True,
        "parity_ok": True,
        "native": use_native,
        "walk_s": round(walk_s, 3),
    })


def retained():
    """BENCH_MODE=retained — subscribe-time retained replay
    (ISSUE 19, docs/DISPATCH.md "Retained replay"). Two phases:

    (a) index A/B: BENCH_SUBS retained NAMES in the RetainIndex,
        mixed literal/wildcard SUBSCRIBE bursts matched through the
        batched ``[F, L] × [cap, L]`` device kernel
        (ops/retained_match.py, device_threshold=0) vs the per-filter
        host scan. The host path IS ``T.match`` over every live name,
        so device==host on the shared burst is the exact-oracle
        parity gate. Host subs/s is measured on a small filter
        subset (RETAINED_HOST_FILTERS) — at 1M names one host filter
        costs seconds, and per-filter cost is the comparable number.

    (b) wire smoke: a live loopback node replays RETAINED_WIRE_TOPICS
        retained messages to RETAINED_WIRE_SUBS simultaneous wildcard
        subscribers through the planner-egress path — every owed
        frame must arrive (zero lost replays), ``retained.replay``
        must count ≤1 batch per SUBSCRIBE, and
        ``delivery.serialize.onloop`` must stay 0 (scripts/ci.sh
        gates these booleans at toy scale).
    """
    import asyncio
    import random as _random

    _jax()

    from emqx_tpu.modules.retainer import RetainIndex
    from emqx_tpu.ops.walk_pallas import walk_variant

    n_names = int(os.environ.get("BENCH_SUBS") or "1000000")
    burst = int(os.environ.get("RETAINED_BURST", "64"))
    n_bursts = int(os.environ.get("RETAINED_BURSTS", "8"))
    host_f = int(os.environ.get("RETAINED_HOST_FILTERS", "4"))
    rng = _random.Random(19)

    t0 = time.time()
    idx = RetainIndex()
    names = [f"s{i % 499}/g{(i // 499) % 97}/d{i}/state"
             for i in range(n_names)]
    for t in names:
        idx.add(t)
    build_s = time.time() - t0

    def mk_burst(k):
        flts = []
        for _ in range(k):
            ws = names[rng.randrange(n_names)].split("/")
            r = rng.random()
            if r < 0.5:
                pass  # literal: exact store probe shape
            elif r < 0.8:
                ws[rng.randrange(len(ws))] = "+"
            else:
                ws = ws[:rng.randint(1, len(ws) - 1)] + ["#"]
            flts.append("/".join(ws))
        return flts

    bursts = [mk_burst(burst) for _ in range(n_bursts)]
    # warm pass: compiles for the (padded-F, cap) shape land here
    idx.match_many(bursts[0], device_threshold=0)
    t0 = time.time()
    dev_hits = [idx.match_many(b, device_threshold=0)
                for b in bursts]
    dev_s = time.time() - t0
    dev_rate = (n_bursts * burst) / dev_s if dev_s else 0.0
    matched = sum(len(h) for hs in dev_hits for h in hs)

    # host half of the A/B + the exact-oracle parity gate: the same
    # filters through the T.match scan must produce the same sets
    probe = bursts[0][:host_f]
    t0 = time.time()
    host_hits = idx.match_many(probe,
                               device_threshold=n_names + 1)
    host_s = time.time() - t0
    host_rate = len(probe) / host_s if host_s else 0.0
    parity_n = len(probe)
    for flt, want in zip(probe, host_hits):
        got = dev_hits[0][bursts[0].index(flt)]
        assert sorted(got) == sorted(want), \
            f"device/host divergence on {flt!r}"
    if n_names <= 20_000:
        # toy scale: full-burst parity is cheap — gate ALL of it
        for b, hs in zip(bursts, dev_hits):
            oracle = idx.match_many(b, device_threshold=n_names + 1)
            assert [sorted(h) for h in hs] \
                == [sorted(h) for h in oracle], "burst parity"
            parity_n += len(b)

    wire = asyncio.run(_retained_wire_smoke())
    assert wire["wire_received"] == wire["wire_expected"], \
        f"lost replays: {wire}"
    assert wire["wire_onloop"] == 0, wire
    assert wire["wire_batches"] <= wire["wire_subs"], wire

    _emit({
        "metric": "retained_subs_per_s",
        "value": round(dev_rate, 1),
        "unit": "subs/sec",
        "workload": "retained_v1",
        "names": n_names,
        "burst": burst,
        "bursts": n_bursts,
        "build_s": round(build_s, 3),
        "matched": matched,
        "host_subs_per_s": round(host_rate, 2),
        "speedup_vs_host": (round(dev_rate / host_rate, 2)
                            if host_rate else None),
        "parity_ok": True,
        "parity_filters": parity_n,
        "walk": walk_variant(),
        **wire,
    })


async def _retained_wire_smoke() -> dict:
    """Phase (b) of BENCH_MODE=retained: live loopback replay with
    the delivery contract pinned (fixed toy scale — it checks
    booleans, not throughput)."""
    import asyncio

    from emqx_tpu.bench_live import _Peer, _count_recv
    from emqx_tpu.modules.retainer import RetainerModule
    from emqx_tpu.mqtt import constants as C
    from emqx_tpu.mqtt.frame import serialize
    from emqx_tpu.mqtt.packet import Publish, Subscribe
    from emqx_tpu.node import Node

    n_topics = int(os.environ.get("RETAINED_WIRE_TOPICS", "64"))
    n_subs = int(os.environ.get("RETAINED_WIRE_SUBS", "8"))
    node = Node(boot_listeners=False)
    node.modules.load(RetainerModule)
    lst = node.add_listener(port=0)
    await node.start()
    try:
        node.modules._loaded["retainer"].index_device_threshold = 0
        pub = _Peer("retw-pub")
        await pub.connect(lst.port)
        for i in range(n_topics):
            pub.writer.write(serialize(Publish(
                topic=f"rw/{i}/s", payload=b"r%d" % i, retain=True),
                C.MQTT_V4))
        await pub.writer.drain()
        deadline = time.time() + 10.0
        while node.metrics.val("retained.count") < n_topics \
                and time.time() < deadline:
            await asyncio.sleep(0.02)
        onloop0 = node.metrics.val("delivery.serialize.onloop")
        subs = [_Peer(f"retw-s{i}") for i in range(n_subs)]
        for i, s in enumerate(subs):
            await s.connect(lst.port)
        tasks = []
        for s in subs:
            # SUBSCRIBE without awaiting the SUBACK: replayed frames
            # can land in the same read as the ack, and the counting
            # loop must see every one of them
            s.writer.write(serialize(Subscribe(
                packet_id=1,
                topic_filters=[("rw/#", {"qos": 0})]), C.MQTT_V4))
            tasks.append(asyncio.ensure_future(_count_recv(s)))
        for s in subs:
            await s.writer.drain()
        expected = n_topics * n_subs
        deadline = time.time() + 30.0
        while sum(s.received for s in subs) < expected \
                and time.time() < deadline:
            await asyncio.sleep(0.02)
        for t in tasks:
            t.cancel()
        for s in subs + [pub]:
            s.close()
        return {
            "wire_topics": n_topics,
            "wire_subs": n_subs,
            "wire_expected": expected,
            "wire_received": sum(s.received for s in subs),
            "wire_onloop":
                node.metrics.val("delivery.serialize.onloop")
                - onloop0,
            "wire_batches":
                node.metrics.val("retained.replay.batches"),
        }
    finally:
        await node.stop()


def overload():
    """BENCH_MODE=overload — the saturation degradation curve
    (offered load vs delivered msgs/s vs shed fraction) through a
    live loopback node with the overload monitor armed
    (emqx_tpu/bench_live.py, docs/ROBUSTNESS.md)."""
    from emqx_tpu.bench_live import overload_curve
    overload_curve(emit=_emit)


def devloss():
    """BENCH_MODE=devloss — the device-loss recovery window: a
    device-regime node loses its backend mid-batch, rides the exact
    host oracle, and auto-recovers (rebuild + kernel rewarm +
    half-open probe). Records host-fallback msgs/s, rebuild_s,
    time-to-breaker-closed, and first-batch-after-recovery p99
    (emqx_tpu/bench_live.py, docs/ROBUSTNESS.md "Device-loss
    recovery")."""
    from emqx_tpu.bench_live import devloss as _devloss
    _devloss(emit=_emit)


def drain():
    """BENCH_MODE=drain — the zero-downtime graceful-drain operation
    (docs/OPERATIONS.md): a 2-node cluster, DRAIN_SESSIONS detached
    persistent sessions + DRAIN_LIVE live clients on the draining
    node; records sessions drained/s, redirect wave p99,
    time-to-empty, and the zero-RPO boolean (digest-verified custody
    hand-off, exactly one holder)."""
    from emqx_tpu.bench_live import drain as _drain
    _drain(emit=_emit)


def fleet():
    """BENCH_MODE=fleet — the connection-fleet row (ISSUE 18):
    FLEET_CONNS real sockets (mostly-idle devices with wills,
    persistent sessions, keepalive pings, reconnect churn) around a
    mixed QoS0/1 + retained + shared-sub traffic core, against
    FLEET_LOOPS event loops / FLEET_WORKERS SO_REUSEPORT processes /
    FLEET_NODES cluster nodes. Records delivered msgs/s, delivery
    p99, RSS per 10K conns, and the counted-blast zero-lost boolean
    (emqx_tpu/bench_live.py; scripts/ci.sh gates a toy-scale run)."""
    from emqx_tpu.bench_live import fleet as _fleet
    _fleet(emit=_emit)


def latency():
    """BENCH_MODE=latency — the small-batch low-latency operating
    point (VERDICT r4 item 4): per-step device latency of the full
    match→pack→expand pipeline at a small batch against the 1M-sub
    trie. A broker is judged on tail latency (the reference bounds
    per-message tails with active_n, src/emqx_connection.erl:99);
    every other row is a throughput batch.

    Methodology: the timed unit is ONE compiled program that runs the
    step CHAIN times sequentially (lax.scan lowers to a while loop —
    strictly serial iterations); per-step latency = wall / CHAIN,
    amortizing the per-sample readback over the chain. Reported
    p50/p99 are over repeated chained samples.
    Fixed bound (BASELINE.md): p99 < 10ms.
    """
    import sys

    chain = int(os.environ.get("BENCH_CHAIN", "32"))
    n_subs = int(os.environ.get("BENCH_SUBS", "1000000"))
    batch = int(os.environ.get("BENCH_BATCH", "8192"))
    iters = int(os.environ.get("BENCH_ITERS", "12"))
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))
    m = int(os.environ.get("BENCH_M", "64"))
    levels = int(os.environ.get("BENCH_LEVELS", "5"))

    jax = _jax()
    from jax import lax

    from emqx_tpu.ops.csr import device_view
    from emqx_tpu.ops.fanout import expand_packed
    from emqx_tpu.ops.match import match_batch, walk_params
    from emqx_tpu.ops.pack import budget_for, pack_matches

    t0 = time.time()
    use_native, cached, host_auto, fan, host_batches, uniques, \
        n_filters, _topics = build_main_inputs(
            n_subs, batch, levels, "mixed", "zipf", 60)
    build_s = time.time() - t0
    k = int(os.environ.get("BENCH_K", "4"))
    auto = jax.device_put(device_view(host_auto))
    fan_d = jax.device_put(fan)
    batches = [jax.device_put(b) for b in host_batches]
    rows = max(b[0].shape[0] for b in batches)
    PM = budget_for(rows, max(8, k))
    Q = budget_for(rows, 16)

    import jax.numpy as jnp

    def jnp_sum32(x):
        return jnp.sum(x, dtype=jnp.int32)

    def one_step(ids, n, sysm):
        res = match_batch(auto, ids, n, sysm, k=k, m=m,
                          pack_ids=False,
                          **walk_params(host_auto, ids.shape[1]))
        m_ptr, packed = pack_matches(res.ids, pm=PM)
        f_ptr, _subs, _src, total = expand_packed(fan_d, m_ptr,
                                                  packed, q=Q)
        return (jnp_sum32(res.count) + jnp_sum32(f_ptr[-1:])
                + jnp_sum32(total[None]))

    def chained(ids, n, sysm):
        def body(carry, _):
            # scan lowers to a while loop: iterations are strictly
            # sequential, so wall/CHAIN is honest per-step latency
            return carry + one_step(ids, n, sysm), None
        out, _ = lax.scan(body, jnp.int32(0), None, length=chain)
        return out

    step = jax.jit(chained)
    for b_ in batches:
        np.asarray(step(*b_))  # compile + warm
    lat = []
    for w in range(windows):
        for i in range(iters):
            t1 = time.perf_counter()
            np.asarray(step(*batches[i % len(batches)]))
            lat.append((time.perf_counter() - t1) * 1000.0 / chain)
    p50 = float(np.percentile(lat, 50))
    p99 = float(np.percentile(lat, 99))
    thr = batch / (p50 / 1000.0)
    info = {
        "mode": "latency", "subs": n_filters, "batch": batch,
        "chain": chain, "k": k, "build_s": round(build_s, 1),
        "build_cached": bool(cached), "native": use_native,
        "avg_unique_topics": round(float(np.mean(uniques)), 1),
        "thr_logical_msgs_per_s": round(thr, 1),
        "device": str(jax.devices()[0]),
    }
    print(json.dumps(info), file=sys.stderr, flush=True)
    _emit({
        "metric": "latency_8k_p99_ms",
        "value": round(p99, 3),
        "unit": "ms",
        # fixed bound: p99 < 10ms at the small-batch operating point
        "vs_baseline": round(10.0 / p99, 3) if p99 > 0 else 0.0,
        "p50_batch_ms": round(p50, 3),
        "p99_batch_ms": round(p99, 3),
        "thr_msgs_per_s": round(thr, 1),
        "chain": chain,
    })


def sharded():
    """BENCH_MODE=sharded — the product multi-chip path: match AND
    per-shard subscriber fan-out through
    ``Router.publish_dispatch_sharded`` (publish_step with real fan
    tables, ``with_fanout=True`` — VERDICT r2 item 3). On the single
    real chip this is mesh (1,1); BENCH_MESH=N uses N devices (the
    virtual CPU mesh in tests). Reports matched+fanned publishes/sec."""
    import sys

    jax = _jax()

    from emqx_tpu.parallel.mesh import default_mesh
    from emqx_tpu.parallel.sharded import (build_sharded_fanout,
                                           place_sharded, shard_of)
    from emqx_tpu.router import MatcherConfig, Router

    rng = random.Random(0)
    n_subs = int(os.environ.get("BENCH_SUBS", "1000000"))
    # default batch = a realistic ingress tick (main() uses 131072
    # logical; the sharded step sees the deduped rows either way)
    B = int(os.environ.get("BENCH_BATCH", "65536"))
    iters = int(os.environ.get("BENCH_ITERS", "30"))
    n_dev = int(os.environ.get("BENCH_MESH", str(len(jax.devices()))))
    d = int(os.environ.get("BENCH_D", "64"))

    mesh = default_mesh(n_dev)
    n_trie = mesh.shape["trie"]
    filters, vocab = build_filters(rng, n_subs, 64)
    r = Router(MatcherConfig(mesh=mesh, fanout_d=d))
    t0 = time.time()
    for f in filters:
        r.add_route(f)
    topics = ["/".join(zipf_choice(rng, lvl) for lvl in vocab[:4])
              for _ in range(B * 4)]
    batches = [(topics[i * B:(i + 1) * B],) for i in range(4)]
    r.match_ids(batches[0][0])  # flatten + match jit warm
    _set_prov(r)
    # one subscriber per subscription, rows on the automaton's own
    # stable shard assignment (what FanoutManager.sharded_state builds
    # in the product; built directly here to skip 1M host sub objects)
    rows = [{} for _ in range(n_trie)]
    for f in filters:
        fid = r.filter_id(f)
        rows[shard_of(f, n_trie)][fid] = [fid]
    from emqx_tpu.broker_helper import ShardedFanoutState

    fan = place_sharded(mesh, build_sharded_fanout(
        rows, len(r._id_to_filter)))
    fan_state = ShardedFanoutState(0, 0, fan, None, frozenset(), d)
    provider = (lambda epoch, id_map: fan_state)

    # the product ingress dedups hot topics per tick BEFORE the device
    # (ingress.py; main() measures the same way, reporting logical
    # msgs with the unique rate alongside) — the sharded step gets the
    # same treatment: dedup each batch, pre-encode + pre-place the
    # UNIQUE rows outside the timed window (the ingress overlaps this
    # host half with in-flight device steps)
    prepped = []
    uniques = []
    encode_ms = []
    for (b,) in batches:
        t_enc = time.perf_counter()
        uniq, inv = dedup_topics(b)
        uniques.append(len(uniq))
        prepped.append((uniq, r.encode_place_sharded(uniq),
                        jax.device_put(np.asarray(inv, np.int32))))
        # per-tick host half, reported so the overlap claim is
        # checkable: the ingress can hide this behind a device step
        # only if it is SHORTER than one (see encode_ms vs p50)
        encode_ms.append((time.perf_counter() - t_enc) * 1000.0)

    def step(batch, pl, inv):
        all_ids, subs, src, _bm, ovf, _movf, _, _, _ = \
            r.publish_dispatch_sharded(batch, provider, placed=pl)
        # per-LOGICAL-message expansion: the dedup inverse gathers
        # every duplicate's match row (what broker.publish_fetch does
        # per tick), so the 65536-logical rate carries per-duplicate
        # device work in the timed window (ADVICE r4 item 2)
        import jax.numpy as _jnp

        ids_full = all_ids[inv]
        logical_matches = _jnp.sum(ids_full >= 0, dtype=_jnp.int32)
        # tiny data-dependent views: reading them back forces the
        # whole step (match + gather + collectives + expansion) to
        # completion without shipping the full arrays through the
        # host link
        return subs[:2, :2], ovf[:8], logical_matches

    # warm EVERY batch: deduped batches can straddle a pow-2 padding
    # bucket boundary, and a publish_step compile for the second
    # bucket must not land inside a timed window (same guard as
    # shared(): one compile per distinct unique-shape bucket)
    for p in prepped:
        step(*p)
    build_s = time.time() - t0
    batches_per_s, rates, outs = _throughput_windows(
        step, prepped, max(1, int(os.environ.get("BENCH_WINDOWS", "5"))),
        iters)
    thr = batches_per_s * B
    p50, p99 = _latency_pass(step, prepped, min(iters, 20))
    st = r.drain_device_stats()
    info = {
        "subs": n_subs, "batch": B, "mesh": dict(mesh.shape),
        "fanout": True, "d": d,
        "build_s": round(build_s, 1),
        "avg_unique_topics": round(sum(uniques) / len(uniques), 1),
        "unique_kmsgs_per_s": round(
            batches_per_s * sum(uniques) / len(uniques) / 1e3, 1),
        "encode_ms": round(sum(encode_ms) / len(encode_ms), 1),
        "dev_matches": st["matches"],
        "dev_deliveries": st["deliveries"],
        "dev_overflows": st["overflows"],
        "device": str(jax.devices()[0]),
        "window_mmsgs": [round(w * B / 1e6, 2) for w in rates],
    }
    print(json.dumps(info), file=sys.stderr, flush=True)
    _emit({
        # renamed from round-2's match-only 'sharded_match_throughput':
        # this mode now measures match+fanout — a different workload
        # must not share a metric key with the old one. The round-4
        # methodology change (raw batches → product-faithful deduped
        # ticks, default tick 4096 → 65536) keeps the key but stamps
        # `workload` so values across the change are distinguishable
        # (the same-series rule, carried by a field instead of a
        # rename: the mode's staged-skip and fail-soft records key on
        # the metric name)
        "metric": "sharded_publish_throughput",
        # v3: 1×1 mesh runs the plain-jit fast path (same program,
        # collectives are identity on one device) and the timed step
        # now includes the per-logical-message dedup-inverse
        # expansion (ADVICE r4 item 2) — a methodology change, so the
        # stamp invalidates staged v2 records
        "workload": "deduped_tick_v3_invexp",
        "value": round(thr, 1),
        "unit": "msgs/sec",
        "vs_baseline": round(thr / 1e6, 3),
        "p50_batch_ms": round(p50, 3),
        "p99_batch_ms": round(p99, 3),
        # the host half per tick, in the staged record so the overlap
        # claim (encode hides behind a device step) is checkable
        # against p50_batch_ms from the artifact alone
        "encode_ms": info["encode_ms"],
        "avg_unique_topics": info["avg_unique_topics"],
    })


def churn():
    """BENCH_MODE=churn — match latency under route churn (VERDICT
    round-1 item 4: 10k subscribe/s against a large filter set must
    leave match p99 unaffected; rebuild cost amortized by O(delta)
    patches, reference O(depth) semantics src/emqx_trie.erl:82-116).

    Three churn shapes (ISSUE 4, docs/MATCH_CACHE.md "Partitioned
    epochs"), all against the same router/filter set:

      - **disjoint** (the headline): literal-rooted churn filters
        (``churn/{i}/leaf``) whose first level is disjoint from the
        matched topics' roots — partitioned epoch keys keep the other
        partitions' cached entries valid, so the hit rate survives;
      - **root_wildcard**: ``+/churnrw/{i}`` — every mutation is a
        global epoch bump (the conservative fallback), hit rate
        collapses by design, exactly as safe as whole-epoch;
      - **share**: ``$share/<group>/churnsh{i}/leaf`` — partitions on
        the level AFTER the share prefix.

    Plus a partitioned-vs-whole-epoch A/B column: the disjoint pass
    re-run with whole-epoch invalidation (``CHURN_PARTITIONS=1``
    semantics, the PR-1 behavior) on the identical filter set.
    ``CHURN_PARTITIONS=<n>`` pins the main passes' granularity (``1``
    makes the headline itself whole-epoch and skips the A/B).

    Reports p99 batch-match latency WITH churn; ``vs_baseline`` is
    the no-churn p99 / churn p99 ratio (1.0 = unaffected)."""
    import sys
    import threading

    jax = _jax()

    from emqx_tpu.router import MatcherConfig, Router

    rng = random.Random(0)
    n_subs = int(os.environ.get("BENCH_SUBS", "1000000"))
    B = int(os.environ.get("BENCH_BATCH", "256"))
    rate = int(os.environ.get("BENCH_CHURN_RATE", "10000"))
    iters = int(os.environ.get("BENCH_ITERS", "60"))
    p_env = int(os.environ.get("CHURN_PARTITIONS", "0"))

    cfg = MatcherConfig() if p_env <= 0 \
        else MatcherConfig(cache_partitions=p_env)
    filters, vocab = build_filters(rng, n_subs, 64)
    r = Router(cfg)
    t0 = time.time()
    for f in filters:
        r.add_route(f)
    topics = ["/".join(zipf_choice(rng, lvl) for lvl in vocab[:4])
              for _ in range(B * 8)]
    batches = [(topics[i * B:(i + 1) * B],) for i in range(8)]
    r.match_ids(batches[0][0])  # flatten + match-kernel jit warm
    r.add_route("warm/patch/path")  # drain-scatter jit warm (fixed
    r.match_ids(batches[0][0])      # chunk shape: compiles once, here)
    r.delete_route("warm/patch/path")
    r.match_ids(batches[0][0])

    # warm every (hit-pad, miss-pad) cache shape the churn passes can
    # produce: with partitioned epochs a churn batch is a PARTIAL
    # hit/miss split (pre-partition churn was all-miss), and each new
    # pow2 pad combo recompiles the merge/insert jits + the walk's
    # miss bucket. One small batch per distinct shape here, so the
    # timed p99 measures steady state, not first-touch XLA.
    hot = list(dict.fromkeys(topics))[:B]
    r.match_ids(hot)  # all cached now
    def _p2(n, floor=8):
        out = floor
        while out < n:
            out *= 2
        return out
    fresh_i = [0]
    seen_sigs = set()
    for m in range(1, B + 1):
        sig = (_p2(max(B - m, 1)), _p2(m))
        if sig in seen_sigs:
            continue
        seen_sigs.add(sig)
        fresh = [f"wfresh/{fresh_i[0] + j}/x" for j in range(m)]
        fresh_i[0] += m
        r.match_ids(hot[:B - m] + fresh)
    build_s = time.time() - t0

    def step(batch):
        _, ids_np, _, _, _ = r.match_ids(batch)
        return ids_np

    p50_base, p99_base = _latency_pass(step, batches, iters)

    def churn_pass(mk):
        """One timed pass under a churner adding/deleting ``mk(i)``
        filters at `rate`/s. Strict add→delete pairing (the old
        alternating loop's ``churn/{i-1}`` arithmetic could delete a
        route it never added); the trailing add is cleaned up after
        join so every pass leaves the filter set exactly as it found
        it (the A/B passes must measure identical sets). Returns
        (p50, p99, achieved rate, cache hit rate DURING the pass,
        route-op p99 ms) — the route-op percentile is the churn
        plane's own latency, the number the off-lock compaction and
        delta batching exist to hold down (ISSUE 7)."""
        c = r._match_cache_obj
        h0, m0 = (c.hits, c.misses) if c is not None else (0, 0)
        stop = threading.Event()
        churned = [0]
        holder = {"pending": None}
        op_lat = []

        def churner():
            i = 0
            interval = 1.0 / max(1, rate)
            next_t = time.perf_counter()
            while not stop.is_set():
                t_op = time.perf_counter()
                if holder["pending"] is None:
                    holder["pending"] = mk(i)
                    r.add_route(holder["pending"])
                    i += 1
                else:
                    r.delete_route(holder["pending"])
                    holder["pending"] = None
                op_lat.append(time.perf_counter() - t_op)
                churned[0] += 1
                next_t += interval
                pause = next_t - time.perf_counter()
                if pause > 0:
                    time.sleep(pause)

        th = threading.Thread(target=churner, daemon=True)
        t1 = time.time()
        th.start()
        p50c, p99c = _latency_pass(step, batches, iters)
        stop.set()
        th.join(timeout=5)
        wall = time.time() - t1
        if holder["pending"] is not None:
            r.delete_route(holder["pending"])
            holder["pending"] = None
        c = r._match_cache_obj
        hd = (c.hits - h0) if c is not None else 0
        md = (c.misses - m0) if c is not None else 0
        hit_rate = hd / max(1, hd + md)
        route_p99 = (float(np.percentile(
            np.array(op_lat) * 1000.0, 99)) if op_lat else 0.0)
        return (p50c, p99c, round(churned[0] / max(wall, 1e-9), 1),
                round(hit_rate, 4), round(route_p99, 3))

    _set_prov(r)
    # warm the delta plane with one UNTIMED churn pass: the side-
    # automaton's capacity-growth ladder, the packed-union and
    # tombstone-mask kernels, and each wildcard shape all compile
    # here — the timed passes measure steady state, not first-touch
    # XLA (same discipline as the cache-shape sweep above)
    churn_pass(lambda i: f"warmd/{i}/leaf")
    churn_pass(lambda i: f"+/warmrw/{i}")
    r.rebuild()  # fold warm deltas: every pass starts from the same
    # compacted tables (shapes stay compiled; state does not linger)
    for b_, in batches:  # re-warm the cache the fold invalidated
        r.match_ids(b_)
    p50_churn, p99_churn, rate_disj, hit_disj, route_p99 = \
        churn_pass(lambda i: f"churn/{i}/leaf")
    _, p99_rw, _, hit_rw, _ = churn_pass(lambda i: f"+/churnrw/{i}")
    _, p99_sh, _, hit_sh, _ = \
        churn_pass(lambda i: f"$share/churngrp/churnsh{i}/leaf")
    # whole-epoch A/B on the SAME router/filter set: the bump
    # granularity is read from the config at mutation time, so
    # flipping it to 1 measures exactly the legacy invalidation on an
    # identical automaton (existing partitioned-key entries go stale
    # on first probe — irrelevant under churn, where whole-epoch
    # invalidates everything every mutation anyway)
    p99_whole = hit_whole = None
    if r.config.cache_partitions > 1:
        parts_used = r.config.cache_partitions
        r.config.cache_partitions = 1
        _, p99_whole, _, hit_whole, _ = \
            churn_pass(lambda i: f"churn/{i}/leaf")
        r.config.cache_partitions = parts_used

    # delta on/off A/B on the SAME router/filter set (ISSUE 7):
    # set_delta folds pending state through one rebuild, so both
    # passes measure an identical automaton — only the churn-plane
    # machinery differs (side-automaton two-probe vs patch-in-place)
    p99_delta_off = hit_delta_off = route_p99_off = None
    delta_was = r.config.delta
    if delta_was:
        r.set_delta(False)
        r.add_route("warm/patch/path")   # drain-scatter jit warm for
        r.match_ids(batches[0][0])       # the patch-in-place pass
        r.delete_route("warm/patch/path")
        r.match_ids(batches[0][0])
        _, p99_delta_off, _, hit_delta_off, route_p99_off = \
            churn_pass(lambda i: f"churn/{i}/leaf")
        r.set_delta(True)

    # steady-state compaction cost: the persistent trie makes a
    # rebuild FLATTEN-ONLY — A/B against a fresh-engine rebuild that
    # must re-insert the whole filter set first (what an off-lock
    # design without the freeze protocol would pay per compaction)
    t_c = time.perf_counter()
    r.rebuild()
    compaction_flatten_s = time.perf_counter() - t_c
    fresh_rebuild_s = fresh_insert_s = None
    if os.environ.get("CHURN_FRESH_AB", "1") != "0":
        from emqx_tpu.ops.csr import device_view as _dview

        t_f = time.perf_counter()
        if r._native is not None:
            from emqx_tpu.ops import native as _native_mod

            eng = _native_mod.NativeEngine()
            for i, f in enumerate(r.topics()):
                eng.insert(f, i)
            fresh_insert_s = time.perf_counter() - t_f
            host = eng.flatten()
            del eng
        else:
            from emqx_tpu.ops.csr import build_automaton as _build
            from emqx_tpu.oracle import TrieOracle as _TO
            from emqx_tpu.ops.tokenize import WordTable as _WT

            trie, table = _TO(), _WT()
            fids = {}
            for i, f in enumerate(r.topics()):
                trie.insert(f)
                fids[f] = i
                for w in f.split("/"):
                    if w not in ("+", "#"):
                        table.intern(w)
            fresh_insert_s = time.perf_counter() - t_f
            host = _build(trie, fids, table)
        # a usable rebuild ends with tables ON DEVICE, exactly like
        # the persistent path's rebuild() — excluding placement would
        # flatter the fresh baseline
        if r.config.use_device:
            jax.block_until_ready(jax.device_put(_dview(host)))
        fresh_rebuild_s = time.perf_counter() - t_f
    st = r.stats()
    bumps = r.cache_bump_totals()
    info = {
        "subs": n_subs, "batch": B, "build_s": round(build_s, 1),
        "churn_target_rate": rate,
        "churn_achieved_rate": rate_disj,
        "p50_ms_no_churn": round(p50_base, 3),
        "p99_ms_no_churn": round(p99_base, 3),
        "p50_ms_churn": round(p50_churn, 3),
        "rebuilds": st["rebuilds"], "patches": st["patches"],
        "bump_global": bumps["global"],
        "bump_partition": bumps["partition"],
        "device": str(jax.devices()[0]),
    }
    print(json.dumps(info), file=sys.stderr, flush=True)
    _emit({
        "metric": "churn_match_p99_ms",
        # ISSUE 7: the online delta automaton — the headline is now
        # measured with route churn absorbed by the side-automaton
        # (main tables pristine) and compaction off-lock; the stamp
        # invalidates staged partitioned_epochs_v1 rows (different
        # churn-plane machinery under the same metric name)
        "workload": "delta_automaton_v1",
        "value": round(p99_churn, 3),
        "unit": "ms",
        "vs_baseline": round(p99_base / p99_churn, 3)
        if p99_churn > 0 else 0.0,
        "p50_batch_ms": round(p50_churn, 3),
        "p99_batch_ms": round(p99_churn, 3),
        "cache_partitions": r.config.cache_partitions,
        "cache_hit_rate_churn": hit_disj,
        # churn-plane latency: the route op itself (ISSUE 7 — the
        # number the delta/off-lock design holds down)
        "route_op_p99_ms": route_p99,
        # variant rows: conservative global-bump shapes
        "root_wildcard_p99_ms": round(p99_rw, 3),
        "root_wildcard_hit_rate": hit_rw,
        "share_p99_ms": round(p99_sh, 3),
        "share_hit_rate": hit_sh,
        # whole-epoch A/B (None when CHURN_PARTITIONS=1 made the
        # headline itself whole-epoch)
        "whole_epoch_p99_ms": round(p99_whole, 3)
        if p99_whole is not None else None,
        "whole_epoch_hit_rate": hit_whole,
        "partition_speedup": round(p99_whole / p99_churn, 3)
        if p99_whole and p99_churn > 0 else None,
        # delta on/off A/B on the identical router/filter set
        "delta_off_p99_ms": round(p99_delta_off, 3)
        if p99_delta_off is not None else None,
        "delta_off_hit_rate": hit_delta_off,
        "delta_speedup": round(p99_delta_off / p99_churn, 3)
        if p99_delta_off and p99_churn > 0 else None,
        "route_op_p99_ms_delta_off": route_p99_off,
        "route_op_speedup": round(route_p99_off / route_p99, 3)
        if route_p99_off and route_p99 > 0 else None,
        "delta_merges": r.delta_info()["merges"],
        "rebuild_stall_ms": r.delta_info()["rebuild_stall_ms"],
        # steady-state compaction: persistent-trie flatten-only vs a
        # fresh-engine re-insert rebuild (the ≥3× acceptance row)
        "compaction_flatten_s": round(compaction_flatten_s, 3),
        "fresh_rebuild_s": round(fresh_rebuild_s, 3)
        if fresh_rebuild_s is not None else None,
        "fresh_insert_s": round(fresh_insert_s, 3)
        if fresh_insert_s is not None else None,
        "persistent_speedup": round(
            fresh_rebuild_s / compaction_flatten_s, 2)
        if fresh_rebuild_s and compaction_flatten_s > 0 else None,
    })


def flapstorm():
    """BENCH_MODE=flapstorm — sustained reconnect storm of a large
    subscriber population (ISSUE 7 acceptance): ``FLAP_PCT_PER_MIN``
    (default 10) percent of ``BENCH_SUBS`` churns per minute — each
    reconnect unsubscribes and resubscribes its filter, the
    mobile-fleet shape — while the publish match plane keeps serving
    with bounded p99 and a stable cache hit rate. A dedicated hot
    subset crash-loops hard enough to cross the ``emqx_flapping``
    threshold and gets auto-banned (every reconnect consults
    ``Banned.check``, as the product CONNECT path does), and session
    takeovers keep flowing through the ConnectionManager against
    channels of churning clients. Reports storm-time match p99 (vs a
    storm-free base), hit rate, route-op p99, ban count and takeover
    p99."""
    import sys
    import threading

    jax = _jax()

    from emqx_tpu.banned import Banned
    from emqx_tpu.cm import ConnectionManager
    from emqx_tpu.flapping import Flapping, FlappingConfig
    from emqx_tpu.router import MatcherConfig, Router
    from emqx_tpu.session import Session

    rng = random.Random(0)
    n_subs = int(os.environ.get("BENCH_SUBS", "1000000"))
    B = int(os.environ.get("BENCH_BATCH", "256"))
    duration = float(os.environ.get("FLAP_SECONDS", "30"))
    pct_min = float(os.environ.get("FLAP_PCT_PER_MIN", "10"))

    filters, vocab = build_filters(rng, n_subs, 64)
    r = Router(MatcherConfig())
    t0 = time.time()
    for f in filters:
        r.add_route(f)
    topics = ["/".join(zipf_choice(rng, lvl) for lvl in vocab[:4])
              for _ in range(B * 8)]
    batches = [(topics[i * B:(i + 1) * B],) for i in range(8)]
    r.match_ids(batches[0][0])  # flatten + match jit warm
    # warm the partial hit/miss cache shapes a storm batch can take
    # (same sweep as BENCH_MODE=churn — without it the timed p99
    # measures first-touch XLA, not the storm)
    hot = list(dict.fromkeys(topics))[:B]
    r.match_ids(hot)
    # make the DELTA active before the shape sweep: flap a depth-
    # representative sample of the population (delete+re-add), so the
    # sweep below compiles the tombstone mask, side-automaton walk
    # and packed-union kernels at every (hit-pad, miss-pad) combo —
    # not the timed window. The pending warm deltas stay live so the
    # storm continues on the same compiled shapes.
    wrng = random.Random(9)
    for idx in wrng.sample(range(len(filters)), min(32, len(filters))):
        r.delete_route(filters[idx])
        r.add_route(filters[idx])

    def _p2(n, floor=8):
        out = floor
        while out < n:
            out *= 2
        return out

    fresh_i = [0]
    seen_sigs = set()
    for m in range(1, B + 1):
        sig = (_p2(max(B - m, 1)), _p2(m))
        if sig in seen_sigs:
            continue
        seen_sigs.add(sig)
        fresh = [f"wfresh/{fresh_i[0] + j}/x" for j in range(m)]
        fresh_i[0] += m
        r.match_ids(hot[:B - m] + fresh)
    for (b,) in batches:
        r.match_ids(b)
    build_s = time.time() - t0
    _set_prov(r)

    def step(batch):
        _, ids_np, _, _, _ = r.match_ids(batch)
        return ids_np

    p50_base, p99_base = _latency_pass(step, batches, 30)

    flapping = Flapping(
        banned=Banned(),
        config=FlappingConfig(max_count=15, window=60.0,
                              ban_time=300.0))
    cm = ConnectionManager()

    class _Chan:
        __slots__ = ("client_id", "session")

        def __init__(self, cid, sess):
            self.client_id = cid
            self.session = sess

        def takeover_begin(self):
            return self.session

        def takeover_end(self, rc):
            pass

    stop = threading.Event()
    counts = {"reconnects": 0, "ban_rejects": 0, "takeovers": 0}
    op_lat: list = []
    tko_lat: list = []
    # the crash-loopers: a small fleet stuck in a tight
    # connect/crash cycle — their rate is a property of the crash
    # loop (~5 reconnects/s each), NOT of the population size, so
    # they cross the flapping threshold (15-in-60s) within seconds
    # at any scale
    flap_ids = [f"flap-{i}" for i in range(8)]
    churn_rate = max(1.0, n_subs * pct_min / 100.0 / 60.0)

    c = r._match_cache_obj
    h0, m0 = (c.hits, c.misses) if c is not None else (0, 0)

    def storm():
        srng = random.Random(1)
        interval = 1.0 / churn_rate
        i = 0
        next_t = time.perf_counter()
        while not stop.is_set():
            idx = srng.randrange(len(filters))
            cid = f"c-{idx}"
            f = filters[idx]
            t_op = time.perf_counter()
            # the reconnect: session drops (unsubscribe), flap
            # tracking, ban gate, resubscribe
            r.delete_route(f)
            flapping.disconnected(cid)
            if flapping.banned.check(clientid=cid):
                counts["ban_rejects"] += 1
            r.add_route(f)  # population clients never cross the bar
            op_lat.append(time.perf_counter() - t_op)
            counts["reconnects"] += 1
            i += 1
            next_t += interval
            pause = next_t - time.perf_counter()
            if pause > 0:
                time.sleep(pause)

    def crash_loop():
        i = 0
        while not stop.is_set():
            fcid = flap_ids[i % len(flap_ids)]
            flapping.disconnected(fcid)
            if flapping.banned.check(clientid=fcid):
                counts["ban_rejects"] += 1
            i += 1
            time.sleep(0.025)  # ~5 reconnects/s per flapper

    def takeovers():
        j = 0
        while not stop.is_set():
            cid = f"tko-{j % 256}"
            old = cm.lookup_channel(cid)
            ch = _Chan(cid, Session(cid, clean_start=False))
            t_op = time.perf_counter()
            if old is None:
                cm.register_channel(cid, ch)
            else:
                cm.open_session(cid, clean_start=False, channel=ch)
                counts["takeovers"] += 1
                tko_lat.append(time.perf_counter() - t_op)
            j += 1
            time.sleep(0.002)

    th_storm = threading.Thread(target=storm, daemon=True)
    th_flap = threading.Thread(target=crash_loop, daemon=True)
    th_tko = threading.Thread(target=takeovers, daemon=True)
    t1 = time.time()
    th_storm.start()
    th_flap.start()
    th_tko.start()
    lat = []
    while time.time() - t1 < duration:
        for i in range(len(batches)):
            t_b = time.perf_counter()
            np.asarray(step(*batches[i]))
            lat.append((time.perf_counter() - t_b) * 1000.0)
    stop.set()
    th_storm.join(timeout=5)
    th_flap.join(timeout=5)
    th_tko.join(timeout=5)
    wall = time.time() - t1
    p50_storm = float(np.percentile(lat, 50))
    p99_storm = float(np.percentile(lat, 99))
    c = r._match_cache_obj
    hd = (c.hits - h0) if c is not None else 0
    md = (c.misses - m0) if c is not None else 0
    hit_rate = hd / max(1, hd + md)
    banned_n = sum(
        1 for fc in flap_ids
        if flapping.banned.look_up("clientid", fc) is not None)
    route_p99 = (float(np.percentile(np.array(op_lat) * 1000.0, 99))
                 if op_lat else 0.0)
    tko_p99 = (float(np.percentile(np.array(tko_lat) * 1000.0, 99))
               if tko_lat else 0.0)
    info = {
        "mode": "flapstorm", "subs": n_subs,
        "build_s": round(build_s, 1),
        "pct_per_min": pct_min,
        "achieved_churn_per_s": round(
            counts["reconnects"] / max(wall, 1e-9), 1),
        "reconnects": counts["reconnects"],
        "delta": r.delta_info(),
        "device": str(jax.devices()[0]),
    }
    print(json.dumps(info), file=sys.stderr, flush=True)
    _emit({
        "metric": "flapstorm_match_p99_ms",
        "workload": "flapstorm_v1",
        "value": round(p99_storm, 3),
        "unit": "ms",
        # 1.0 = the storm is invisible to the match plane
        "vs_baseline": round(p99_base / p99_storm, 3)
        if p99_storm > 0 else 0.0,
        "p50_batch_ms": round(p50_storm, 3),
        "p99_batch_ms": round(p99_storm, 3),
        "p99_ms_no_storm": round(p99_base, 3),
        "pct_per_min": pct_min,
        "achieved_churn_per_s": info["achieved_churn_per_s"],
        "cache_hit_rate_storm": round(hit_rate, 4),
        "route_op_p99_ms": round(route_p99, 3),
        "flappers_banned": banned_n,
        "ban_rejects": counts["ban_rejects"],
        "takeovers": counts["takeovers"],
        "takeover_p99_ms": round(tko_p99, 3),
        "delta_merges": r.delta_info()["merges"],
        "rebuild_stall_ms": r.delta_info()["rebuild_stall_ms"],
    })


def recovery():
    """BENCH_MODE=recovery — the durability layer's two costs
    (ISSUE 9): journal-append overhead on the live publish path
    (durability on/off A/B msgs/s with a durable QoS1 subscriber
    fleet — every delivery/ack dirties session state, every batch
    pays one coalesced journal flush) and crash-recovery time vs
    route count (``recovery_replay_s`` / ``recovery_routes``: full
    journal replay + session resurrection + baseline checkpoint,
    the kill -9 worst case with no checkpoint to shortcut)."""
    import asyncio
    import shutil
    import sys
    import tempfile

    jax = _jax()

    from emqx_tpu.durability import DurabilityConfig
    from emqx_tpu.node import Node
    from emqx_tpu.session import Session
    from emqx_tpu.types import Message, SubOpts

    n_routes = int(os.environ.get(
        "RECOVERY_ROUTES", os.environ.get("BENCH_SUBS", "100000")))
    B = int(os.environ.get("BENCH_BATCH", "256"))
    pub_iters = int(os.environ.get("RECOVERY_PUB_ITERS", "20"))
    use_fsync = os.environ.get("RECOVERY_FSYNC", "1") == "1"
    wal_shards = int(os.environ.get("RECOVERY_SHARDS", "4"))
    ckpt_churn = int(os.environ.get("RECOVERY_CKPT_CHURN", "64"))
    n_sessions = min(int(os.environ.get("RECOVERY_SESSIONS", "1000")),
                     n_routes)
    rng = random.Random(0)
    filters = [f"rb/{i}/s" for i in range(n_routes)]
    pub_topics = [filters[rng.randrange(n_routes)]
                  for _ in range(B * 8)]
    batches = [pub_topics[i * B:(i + 1) * B] for i in range(8)]

    def _drain_acks(sessions):
        for s in sessions:
            for pid, item in s.drain_outbox():
                if isinstance(pid, int):
                    s.puback(pid)

    async def _build(durable, d):
        cfg = (DurabilityConfig(enabled=True, dir=d, fsync=use_fsync,
                                wal_shards=wal_shards)
               if durable else None)
        node = Node(boot_listeners=False, durability=cfg,
                    load_default_modules=True)
        await node.start()
        sessions = []
        per = n_routes // n_sessions
        for i in range(n_sessions):
            s = Session(f"dev-{i}", broker=node.broker,
                        clean_start=False, max_inflight=0)
            if durable:
                node.durability.session_opened(s, 3600.0)

                class _Ch:
                    def __init__(self, sess):
                        self.session = sess
                node.cm.register_channel(s.client_id, _Ch(s))
            for f in filters[i * per:(i + 1) * per]:
                s.subscribe(f, SubOpts(qos=1))
            sessions.append(s)
        return node, sessions

    def _window(node, sessions, durable, iters):
        sent = 0
        t1 = time.perf_counter()
        for it in range(iters):
            b = batches[it % len(batches)]
            node.broker.publish_batch(
                [Message(topic=t, payload=b"x", qos=1) for t in b])
            _drain_acks(sessions)
            if durable:
                # the batched journal flush the ingress executor
                # pays per tick on the socket path
                node.durability.on_batch()
            sent += len(b)
        return sent / max(time.perf_counter() - t1, 1e-9)

    async def _run():
        out = {}
        dirs = [tempfile.mkdtemp(prefix="emqx_dur_bench_")
                for _ in range(2)]
        # both nodes built and warmed BEFORE either timed window —
        # process-level XLA compile caching must not subsidize
        # whichever variant runs second
        node_off, sess_off = await _build(False, dirs[0])
        node_on, sess_on = await _build(True, dirs[1])
        for node, sessions, durable in ((node_off, sess_off, False),
                                        (node_on, sess_on, True)):
            _window(node, sessions, durable, len(batches))
        out["msgs_per_s_off"] = _window(node_off, sess_off, False,
                                        pub_iters)
        out["msgs_per_s_on"] = _window(node_on, sess_on, True,
                                       pub_iters)
        wi = node_on.durability.wal.info()
        out["journal_records"] = wi["records"]
        out["journal_mb"] = round(wi["bytes"] / 1e6, 2)
        out["last_fsync_ms"] = wi["last_fsync_ms"]
        out["group_commits"] = wi["group_commits"]
        # crash the durable node: abandon without graceful shutdown
        # — the recovery below replays the whole journal
        node_on.broker.durability = None
        node_on.cm.durability = None
        node_on.durability = None
        crash_dir = dirs[1]
        await node_off.stop()
        await node_on.stop()

        t2 = time.perf_counter()
        node2 = Node(boot_listeners=False,
                     durability=DurabilityConfig(
                         enabled=True, dir=crash_dir,
                         fsync=use_fsync),
                     load_default_modules=True)
        await node2.start()
        out["recovery_total_s"] = round(time.perf_counter() - t2, 3)
        rec = node2.durability.last_recovery
        out["recovery_replay_s"] = rec["duration_s"]
        out["recovered_sessions"] = rec["sessions"]
        out["replayed_records"] = rec["replayed_records"]
        out["recovered_routes"] = rec["routes"]
        # incremental-checkpoint cost A/B on the recovered node (it
        # holds the full-scale table): a FULL rebase pays the whole
        # table; a DELTA after a small churn burst must cost ~the
        # churn — the acceptance gate is that delta time tracks
        # churn, not route count (docs/DURABILITY.md)
        t_f0 = time.perf_counter()
        node2.durability.checkpoint_now(full=True)
        out["ckpt_full_s"] = round(time.perf_counter() - t_f0, 4)
        det = [ent[0] for ent in node2.cm._detached.values()]
        for i in range(ckpt_churn if det else 0):
            det[i % len(det)].subscribe(
                f"ckpt/churn/{i}", SubOpts(qos=1))
        node2.durability.on_batch()
        t_d0 = time.perf_counter()
        ck = node2.durability.checkpoint_now(full=False)
        out["ckpt_delta_s"] = round(time.perf_counter() - t_d0, 4)
        out["ckpt_delta_records"] = ck.get("records")
        await node2.stop()
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)
        return out

    r = asyncio.run(_run())

    def _gc_window_sweep():
        """ROADMAP item 5d: measure what group_commit_window_ms
        actually buys. T concurrent flushers (the multi-loop shape)
        hammer one fsync-armed WalGroup per window value; the sweep
        records fsyncs per flush call (coalescing win) against the
        added p50/p99 flush latency (the window's cost) — the
        docs/DURABILITY.md recommendation table is generated from
        exactly these columns."""
        import tempfile
        import threading as th

        from emqx_tpu.wal import WalGroup

        windows = [float(x) for x in os.environ.get(
            "RECOVERY_GC_WINDOWS", "0,1,3,10").split(",")]
        T = int(os.environ.get("RECOVERY_GC_THREADS", "4"))
        flushes = int(os.environ.get("RECOVERY_GC_FLUSHES", "50"))
        recs = int(os.environ.get("RECOVERY_GC_RECS", "32"))
        rows = []
        for w_ms in windows:
            d = tempfile.mkdtemp(prefix="emqx_gc_sweep_")
            wg = WalGroup(d, 1, shards=max(2, T), fsync=True,
                          group_window_ms=w_ms)
            lats: list = []
            lk = th.Lock()

            def _worker(ti):
                mine = []
                for i in range(flushes):
                    for j in range(recs):
                        wg.append(("route", f"g/{ti}/{i}/{j}",
                                   "bench", 1), key=f"k{ti}-{j}")
                    t0 = time.perf_counter()
                    wg.flush()
                    mine.append(
                        (time.perf_counter() - t0) * 1000.0)
                with lk:
                    lats.extend(mine)

            threads = [th.Thread(target=_worker, args=(t,))
                       for t in range(T)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            wi = wg.info()
            wg.close()
            shutil.rmtree(d, ignore_errors=True)
            lats.sort()
            n = len(lats)
            rows.append({
                "window_ms": w_ms,
                "fsyncs": wi["fsyncs"],
                "fsyncs_per_flush": round(
                    wi["fsyncs"] / max(n, 1), 3),
                "group_commits": wi["group_commits"],
                "coalesced": wi["group_coalesced"],
                "flush_p50_ms": round(lats[n // 2], 3),
                "flush_p99_ms": round(
                    lats[min(n - 1, int(n * 0.99))], 3),
                "flushes_per_s": round(n / max(wall, 1e-9)),
                "last_commit_ms": wi["last_commit_ms"],
            })
        return rows

    gc_sweep = None
    if os.environ.get("RECOVERY_GC_SWEEP", "1") == "1":
        gc_sweep = _gc_window_sweep()
    on, off = r["msgs_per_s_on"], r["msgs_per_s_off"]
    info = {"mode": "recovery", "routes": n_routes,
            "sessions": n_sessions, "fsync": use_fsync,
            "wal_shards": wal_shards,
            "device": str(jax.devices()[0])}
    print(json.dumps(info), file=sys.stderr, flush=True)
    _emit({
        "metric": "recovery_replay_s",
        "workload": "durability_sharded_v1",
        "value": r["recovery_replay_s"],
        "unit": "s",
        "recovery_routes": r["recovered_routes"],
        "recovery_sessions": r["recovered_sessions"],
        "recovery_records": r["replayed_records"],
        "recovery_total_s": r["recovery_total_s"],
        "recovery_records_per_s": round(
            r["replayed_records"] / max(r["recovery_replay_s"],
                                        1e-9)),
        "durability_on_msgs_per_s": round(on),
        "durability_off_msgs_per_s": round(off),
        "durability_overhead_pct": round(
            100.0 * (1.0 - on / max(off, 1e-9)), 1),
        "journal_records": r["journal_records"],
        "journal_mb": r["journal_mb"],
        "last_fsync_ms": r["last_fsync_ms"],
        "fsync": use_fsync,
        "wal_shards": wal_shards,
        "group_commits": r["group_commits"],
        "ckpt_full_s": r["ckpt_full_s"],
        "ckpt_delta_s": r["ckpt_delta_s"],
        "ckpt_delta_records": r["ckpt_delta_records"],
        "ckpt_churn": ckpt_churn,
        "ckpt_speedup": round(
            r["ckpt_full_s"] / max(r["ckpt_delta_s"], 1e-9), 2),
        "gc_window_sweep": gc_sweep,
    })


def _failover_probe():
    """The BENCH_MODE=partition failover + FAILBACK rows
    (docs/DURABILITY.md "Replicated durability" / "Failback"): a
    durable primary journals ``FAILOVER_SESSIONS`` persistent
    sessions (default 5000 — a real fleet, not a toy) + retained +
    routes and ships the stream to a warm standby; the primary is
    killed (kill -9 analogue: durability hooks severed, transport
    dropped) and the standby's heartbeat detector drives promotion.
    Measures failover time (kill → promoted), RPO in records for
    acked traffic (must be 0), and digest-verifies the promoted
    durable planes against the primary's pre-kill state. Then the
    primary RESTARTS from its own directory, rejoins, and the
    promoted standby hands the (post-promotion-churned) state back:
    ``failback_s`` = restart → standby demoted + stream resynced,
    digest-verified against the standby's pre-failback state.
    ``PARTITION_FAILBACK=0`` skips the second hop."""
    import shutil
    import tempfile

    from emqx_tpu.cluster import Cluster, ClusterConfig
    from emqx_tpu.cluster_net import SocketTransport
    from emqx_tpu.durability import DurabilityConfig
    from emqx_tpu.modules.retainer import RetainerModule
    from emqx_tpu.node import Node
    from emqx_tpu.replication import durable_digest
    from emqx_tpu.session import Session
    from emqx_tpu.types import Message, SubOpts

    n_sess = int(os.environ.get("FAILOVER_SESSIONS", "5000"))
    n_ret = int(os.environ.get("FAILOVER_RETAINED", "100"))
    cfg = ClusterConfig(
        heartbeat_interval_s=0.1, heartbeat_timeout_s=0.5,
        suspect_after=1, down_after=3, ok_after=1,
        anti_entropy_interval_s=0.5, call_timeout_s=10.0,
        redial_backoff_s=0.1, redial_backoff_max_s=0.5)

    def _wait(pred, timeout, what):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if pred():
                return
            time.sleep(0.02)
        raise RuntimeError(f"failover probe: {what} not reached "
                           f"within {timeout}s")

    class _Ch:
        def __init__(self, s):
            self.session = s
            self.client_id = s.client_id

    tmp = tempfile.mkdtemp(prefix="emqx_failover_")
    nodes, trs, cls = [], [], []
    try:
        for i in range(2):
            dcfg = None
            if i == 0:
                dcfg = DurabilityConfig(
                    enabled=True, dir=os.path.join(tmp, "d0"),
                    fsync=False, standby="fb1", wal_shards=4)
            node = Node(name=f"fb{i}", boot_listeners=False,
                        durability=dcfg)
            node.modules.load(RetainerModule)
            if node.durability is not None:
                node.durability.recover()
            tr = SocketTransport(f"fb{i}", cookie="bench-failover",
                                 config=cfg)
            tr.serve()
            cls.append(Cluster(node, transport=tr, config=cfg))
            nodes.append(node)
            trs.append(tr)
        cls[1].join_remote("127.0.0.1", trs[0].port)
        n0 = nodes[0]
        sessions = []
        for i in range(n_sess):
            s = Session(f"fdev-{i}", broker=n0.broker,
                        clean_start=False)
            n0.durability.session_opened(s, 3600.0)
            n0.cm.register_channel(s.client_id, _Ch(s))
            s.subscribe(f"fb/{i}/+", SubOpts(qos=1))
            sessions.append(s)
        for i in range(n_ret):
            n0.broker.publish(Message(
                topic=f"fb/{i % max(n_sess, 1)}/state",
                payload=b"v%d" % i, qos=1, flags={"retain": True}))
        n0.durability.on_batch()  # flush + ship: this is the acked set
        r = n0.replication
        _wait(lambda: r.state == "replicating"
              and r.acked_seq >= r.offered_seq, 60, "journal sync")
        acked = r.acked_seq
        for s in sessions:  # digest compares the sessions detached
            n0.cm._detached[s.client_id] = (s, 0, 3600.0)
        want = durable_digest(n0)
        # kill -9: no graceful path, no final ship
        n0.broker.durability = None
        n0.cm.durability = None
        t_kill = time.perf_counter()
        trs[0].close()
        rep1 = nodes[1].replication
        _wait(lambda: "fb0" in rep1.replicas
              and rep1.replicas["fb0"].promoted, 60, "promotion")
        failover_s = time.perf_counter() - t_kill
        got = durable_digest(nodes[1])
        lp = rep1.last_promotion
        out = {
            "failover_s": round(failover_s, 3),
            "failover_promote_s": lp["failover_s"],
            "failover_sessions": lp["sessions"],
            "failover_routes": lp["routes"],
            "rpo_records": max(
                0, acked - rep1.replicas["fb0"].applied_seq),
            "failover_digest_ok": bool(got == want),
            "failback_s": None,
            "failback_sessions": None,
            "failback_digest_ok": None,
        }
        if os.environ.get("PARTITION_FAILBACK", "1") == "1":
            # post-promotion churn the failback must carry home
            nodes[1].broker.publish(Message(
                topic="fb/0/state", payload=b"post-promo", qos=1,
                flags={"retain": True}))
            want2 = durable_digest(nodes[1])
            t_fb = time.perf_counter()
            n0b = Node(name="fb0", boot_listeners=False,
                       durability=DurabilityConfig(
                           enabled=True,
                           dir=os.path.join(tmp, "d0"),
                           fsync=False, standby="fb1",
                           wal_shards=4))
            n0b.modules.load(RetainerModule)
            n0b.durability.recover()
            tr0b = SocketTransport("fb0", cookie="bench-failover",
                                   config=cfg)
            tr0b.serve()
            cl0b = Cluster(n0b, transport=tr0b, config=cfg)
            nodes.append(n0b)
            trs.append(tr0b)
            cls.append(cl0b)
            cl0b.join_remote("127.0.0.1", trs[1].port)
            _wait(lambda: not rep1.replicas["fb0"].promoted, 120,
                  "failback demotion")
            r0 = n0b.replication

            def _resynced():
                # tick the journal flush the started-node timer
                # would run (records journaled by the failback apply
                # must flush to ship)
                n0b.durability.on_batch()
                return (r0.state == "replicating"
                        and r0.acked_seq >= r0.offered_seq)

            _wait(_resynced, 120, "post-failback resync")
            out["failback_s"] = round(
                time.perf_counter() - t_fb, 3)
            out["failback_sessions"] = len(n0b.cm._detached)
            try:
                _wait(lambda: durable_digest(n0b) == want2, 60,
                      "failback digest")
                out["failback_digest_ok"] = True
            except RuntimeError:
                out["failback_digest_ok"] = False
            fb = nodes[1].replication.last_failback
            if fb:
                out["failback_handoff_s"] = fb.get("failback_s")
        return out
    finally:
        for node in nodes:
            d = node.durability
            if d is not None and d.wal is not None:
                d.wal.close()
        for c in cls:
            c.close()
        for tr in trs:
            tr.close()
        shutil.rmtree(tmp, ignore_errors=True)


def partition():
    """BENCH_MODE=partition — the cluster plane's three failure
    numbers (ISSUE 10, docs/CLUSTER.md): detection latency (partition
    armed → both sides observe the membership split via the heartbeat
    detector), heal-to-convergence time (partition disarmed → all
    five replicated plane digests byte-equal across members, zero
    manual rejoin), and data-plane forwards dropped during a timed
    partition window with route churn on BOTH sides of the split.
    Plus (ISSUE 11) the warm-standby FAILOVER row: primary kill →
    standby promotion time, RPO records for acked traffic (0), and a
    digest-verified byte-exactness check — ``PARTITION_FAILOVER=0``
    skips it.

    3 nodes in one process over real sockets, the partition injected
    through the net.partition fault point scoped per transport —
    the same machinery the chaos matrix (tests/test_cluster_heal.py)
    gates, at bench scale."""
    import sys

    jax = _jax()

    from emqx_tpu import faults
    from emqx_tpu.cluster import Cluster, ClusterConfig
    from emqx_tpu.cluster_net import SocketTransport
    from emqx_tpu.node import Node

    n_routes = int(os.environ.get(
        "PARTITION_ROUTES", os.environ.get("BENCH_SUBS", "3000")))
    window_s = float(os.environ.get("PARTITION_SECONDS", "3"))
    cfg = ClusterConfig(
        heartbeat_interval_s=0.1, heartbeat_timeout_s=0.5,
        suspect_after=1, down_after=3, ok_after=1,
        anti_entropy_interval_s=0.5, call_timeout_s=2.0,
        redial_backoff_s=0.1, redial_backoff_max_s=0.5)

    class _Sub:
        def __init__(self, cid):
            self.client_id = cid

        def deliver(self, t, m):
            pass

    def _wait(pred, timeout, what):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if pred():
                return time.perf_counter()
            time.sleep(0.02)
        raise RuntimeError(f"partition bench: {what} not reached "
                           f"within {timeout}s")

    def _converged(cls):
        digests = [c.plane_digests() for c in cls]
        return all(d == digests[0] for d in digests[1:])

    nodes, trs, cls = [], [], []
    try:
        for i in range(3):
            node = Node(name=f"bn{i}", boot_listeners=False)
            tr = SocketTransport(f"bn{i}", cookie="bench-part",
                                 config=cfg)
            tr.serve()
            cls.append(Cluster(node, transport=tr, config=cfg))
            nodes.append(node)
            trs.append(tr)
        for i in (1, 2):
            cls[i].join_remote("127.0.0.1", trs[0].port)
        subs = []
        for i in range(n_routes):
            s = _Sub(f"bsub-{i}")
            nodes[i % 3].broker.subscribe(s, f"bench/p/{i}")
            subs.append(s)
        _wait(lambda: _converged(cls), 60, "pre-partition sync")
        for c in cls:
            c.drain_counters()  # window counters start clean

        # -- partition {bn0, bn1} | {bn2}, churn on both sides -----
        trs[0].fault_peers = trs[1].fault_peers = {"bn2"}
        trs[2].fault_peers = {"bn0", "bn1"}
        faults.set_master(True)
        t0 = time.perf_counter()
        faults.arm("net.partition", times=0)
        t_detect = _wait(
            lambda: cls[0].members == ["bn0", "bn1"]
            and cls[2].members == ["bn2"], 30, "detection")
        detect_s = t_detect - t0
        churn = 0
        t_end = time.perf_counter() + window_s
        while time.perf_counter() < t_end:
            i = churn % n_routes
            side = nodes[0] if churn % 2 else nodes[2]
            s = _Sub(f"churn-{churn}")
            side.broker.subscribe(s, f"bench/c/{i}")
            side.broker.unsubscribe(s, f"bench/c/{i}")
            churn += 1
            time.sleep(0.002)

        # -- heal: zero manual rejoin --------------------------------
        t1 = time.perf_counter()
        faults.disarm("net.partition")
        _wait(lambda: all(sorted(c.members) == ["bn0", "bn1", "bn2"]
                          for c in cls), 60, "membership re-merge")
        t_conv = _wait(lambda: _converged(cls), 60,
                       "plane-digest convergence")
        heal_s = t_conv - t1
        counters = {}
        for c in cls:
            for k, v in c.drain_counters().items():
                counters[k] = counters.get(k, 0) + v
    finally:
        faults.clear()
        for c in cls:
            c.close()
        for tr in trs:
            tr.close()

    failover = {"failover_s": None, "rpo_records": None,
                "failover_digest_ok": None}
    if os.environ.get("PARTITION_FAILOVER", "1") == "1":
        failover = _failover_probe()

    info = {"mode": "partition", "routes": n_routes,
            "window_s": window_s, "churn_ops": churn,
            "device": str(jax.devices()[0])}
    print(json.dumps(info), file=sys.stderr, flush=True)
    _emit(dict({
        "metric": "partition_heal_converge_s",
        "workload": "cluster_failover_v1",
        "value": round(heal_s, 3),
        "unit": "s",
        "partition_detect_s": round(detect_s, 3),
        "partition_window_s": window_s,
        "partition_churn_ops": churn,
        "forwards_dropped": counters.get("forward.dropped", 0),
        "heal_rejoins": counters.get("heal.rejoins", 0),
        "ae_repairs": counters.get("ae.repairs", 0),
        "hb_downs": counters.get("hb.downs", 0),
        "routes": n_routes,
    }, **failover))


# The BASELINE.json config matrix: one row per driver-defined config,
# plus the uniform-traffic variant (no batch-dedup advantage) and the
# match-cache / latency / retained rows. Each entry: (row name, extra
# env, BENCH_MODE, subscription count).
_CONFIG_MATRIX = [
    # headline FIRST: if the run is cut mid-matrix, the
    # round-over-round metric must already be in the row list
    ("mixed_1m_zipf", {"BENCH_ITERS": "20", "BENCH_WINDOWS": "5"},
     None, 1_000_000),
    ("literal_100k", {"BENCH_MIX": "literal", "BENCH_LEVELS": "1",
                      "BENCH_WPL": "100000"}, None, 100_000),
    ("plus_1m", {"BENCH_MIX": "plus"}, None, 1_000_000),
    # the two compaction A/B rows (ISSUE 16): the deep row is where
    # path compression lives (16-level spines, hops ≪ levels), the
    # uniform row is the guard against the flat-tree regression
    ("hash_1m_deep", {"BENCH_MIX": "hash", "BENCH_LEVELS": "16",
                      "BENCH_COMPRESS_AB": "1"}, None, 1_000_000),
    ("share_1m", {}, "shared", 1_000_000),
    ("mixed_10m", {}, None, 10_000_000),
    ("mixed_1m_uniform",
     {"BENCH_TRAFFIC": "uniform", "BENCH_COMPRESS_AB": "1"}, None,
     1_000_000),
    # match-cache A/B rows (same workloads as the two rows above;
    # the cache-off rows ARE the baseline half of the pair)
    ("mixed_10m_cache", {"BENCH_CACHE": "1"}, None, 10_000_000),
    ("mixed_1m_uniform_cache",
     {"BENCH_TRAFFIC": "uniform", "BENCH_CACHE": "1"}, None,
     1_000_000),
    # small-batch tail-latency operating point: per-step device
    # latency over a compiled chain
    ("latency_8k", {"BENCH_BATCH": "8192", "BENCH_CHAIN": "32"},
     "latency", 1_000_000),
    # subscribe-time retained replay (ISSUE 19): 1M retained names,
    # mixed literal/wildcard bursts, batched-device vs host-scan A/B
    ("retained_1m", {"RETAINED_BURST": "64", "RETAINED_BURSTS": "8"},
     "retained", 1_000_000),
]

_HEADLINE_ROW = "mixed_1m_zipf"


def _last_json_line(text: str):
    """Last '{'-opening line of a stream, parsed — the bench line /
    info line extraction idiom of the orchestrator."""
    lines = [l for l in text.strip().splitlines() if l.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def configs():
    """Default mode: run the full BASELINE config matrix, one bounded
    subprocess per config, and emit ONE record whose ``configs``
    array carries every row. The headline value/latency fields come
    from the 1M-mixed-Zipf workload.

    ONE PROCESS PER CHIP: a chip belongs to one process at a time, so
    this parent never imports JAX (it would hold the chip and every
    child would fail or hang) and the children run strictly one after
    another. A row that fails — no chip included — is an ``error``
    row, and any error row makes the whole run exit non-zero."""
    import subprocess
    import sys

    cfg_timeout = float(os.environ.get("BENCH_CFG_TIMEOUT", "900"))
    # global wall budget: skip (and label) remaining rows rather than
    # letting an outer timeout kill the process before the final
    # JSON line prints
    deadline = time.monotonic() + float(
        os.environ.get("BENCH_DEADLINE", "3000"))
    rows = []
    for name, extra, mode, subs in _CONFIG_MATRIX:
        if time.monotonic() > deadline:
            rows.append({"name": name,
                         "error": "skipped: BENCH_DEADLINE reached"})
            continue
        env = dict(os.environ)
        for k_, v_ in extra.items():
            if k_ in ("BENCH_ITERS", "BENCH_WINDOWS") \
                    and k_ in os.environ:
                continue  # explicit operator effort override wins
            env[k_] = v_
        # an unset BENCH_MODE means `configs` — the child must run
        # the CONCRETE mode or it would recurse into this orchestrator
        env["BENCH_MODE"] = mode or "mixed"
        env["BENCH_SUBS"] = str(subs)
        # per-row effort smaller than a solo run; explicit env wins
        env.setdefault("BENCH_ITERS", "12")
        env.setdefault("BENCH_WINDOWS", "3")
        t0 = time.time()
        row = {"name": name, "subs": subs}
        try:
            budget = min(cfg_timeout,
                         max(60.0, deadline - time.monotonic()))
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                capture_output=True, timeout=budget, env=env,
                text=True)
            rec = _last_json_line(out.stdout)
            if out.returncode != 0 or rec is None:
                tail = out.stderr.strip().splitlines()[-1:] or [""]
                row["error"] = (f"child exited rc={out.returncode}: "
                                f"{tail[0][:200]}")
            else:
                for fld in ("metric", "value", "unit", "vs_baseline",
                            "p50_batch_ms", "p99_batch_ms",
                            "platform", "device_kind",
                            "device_count"):
                    if fld in rec:
                        row[fld] = rec[fld]
                # the child's stderr info line carries the workload
                # context that makes a logical-rate row honest — a
                # Zipf batch can dedup 400x, and without the unique
                # count alongside, the row would overstate itself
                inf = _last_json_line(out.stderr) or {}
                for fld in ("avg_unique_topics", "batch",
                            "build_s", "build_cached", "native",
                            "unique_kmsgs_per_s",
                            "avg_deliveries_per_unique", "k",
                            "overflow_frac",
                            "cache", "cache_slots",
                            "cache_hit_rate",
                            "cache_warm_hit_rate",
                            "walk_levels_p50",
                            "gathers_per_topic",
                            "compress_off_p50_ms",
                            "compress_speedup",
                            "thr_logical_msgs_per_s", "chain"):
                    if fld in inf:
                        row[fld] = inf[fld]
                # measurement effort, recorded per row
                row["iters"] = int(env.get("BENCH_ITERS", "20"))
                row["windows"] = int(env.get("BENCH_WINDOWS", "5"))
        except subprocess.TimeoutExpired:
            row["error"] = f"config timed out > {budget:.0f}s"
        row["wall_s"] = round(time.time() - t0, 1)
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    failed = [r["name"] for r in rows if "error" in r]
    if failed:
        for r in rows:
            if "error" in r:
                print(f"bench.py: row {r['name']}: {r['error']}",
                      file=sys.stderr, flush=True)
        raise SystemExit(
            f"bench.py: {len(failed)} of {len(rows)} rows failed "
            f"({', '.join(failed)}) — no record printed")
    head = next(r for r in rows if r["name"] == _HEADLINE_ROW)
    rec = {"metric": _MATRIX_METRIC, "unit": "msgs/sec",
           "configs": rows}
    for fld in _HEADLINE_FIELDS + ("platform", "device_kind",
                                   "device_count"):
        if fld in head:
            rec[fld] = head[fld]
    print(json.dumps(rec), flush=True)


#: BENCH_MODE values; each names its entry function ("mixed" is main)
_MODES = ("bigfan", "shared", "live", "latency", "churn", "flapstorm",
          "overload", "devloss", "drain", "fleet", "recovery",
          "partition", "sharded", "deep_smoke", "retained", "mixed",
          "configs")


if __name__ == "__main__":
    _mode = os.environ.get("BENCH_MODE") or "configs"
    if _mode not in _MODES:
        raise SystemExit(f"bench.py: unknown BENCH_MODE {_mode!r}")
    if _mode != "configs":
        # the gate, before any work: no chip => non-zero exit and
        # nothing printed (the configs parent stays off JAX — its
        # children each pass this gate themselves). A mode that
        # raises ends the process with a traceback and a non-zero
        # exit; no earlier record is ever read back
        _jax()
    globals()["main" if _mode == "mixed" else _mode]()
