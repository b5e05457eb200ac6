#!/usr/bin/env bash
# The repo's one-command gate, on the CPU. The reference
# gates with dialyzer/xref/elvis + suites in CI
# (/root/reference/rebar.config:27-34, .github/workflows); this image
# has no ruff/mypy/coverage and installs are off-limits, so the gate
# is stdlib-built:
#
#   1. byte-compile everything            (syntax)
#   2. scripts/lint.py --stats            (static-analysis gate:
#      generic smells + concurrency-domain/lock rules + registry-
#      drift cross-checks, docs/ANALYSIS.md; per-rule counts printed,
#      any unwaived finding fails)
#   3. tests/test_lint.py                 (the analyzers' own suite:
#      every rule must catch its seeded violation)
#   4. pytest                             (full suite, CPU mesh)
#   5. scripts/cov.py over the suite      (line coverage report;
#      COV=0 skips — it roughly doubles suite wall time)
#
# Nothing here needs the chip: chiprun -- python chip_smoke.py is the
# on-chip gate (tests/test_chip_smoke.py rehearses it at toy size) and
# python3 benchmark/run.py the measurement (BENCHMARK.json).
#
# Exits nonzero on any violation.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== byte-compile =="
python -m compileall -q emqx_tpu tests scripts chip_smoke.py \
    __graft_entry__.py

echo "== static analysis (scripts/lint.py, docs/ANALYSIS.md) =="
python scripts/lint.py --stats

echo "== analyzer self-tests (tests/test_lint.py) =="
python -m pytest tests/test_lint.py -q

echo "== match-cache parity (docs/MATCH_CACHE.md) =="
# also part of the full suite below; run first so a cache parity
# regression fails the gate before the long run
python -m pytest tests/test_match_cache.py -q

echo "== partitioned-epoch churn parity (docs/MATCH_CACHE.md) =="
# randomized interleaved add/delete/publish against the host oracle
# (literal, root-wildcard, $share, overflow topics; single-chip +
# mesh) incl. the cache_partitions=1 whole-epoch A/B guard — a
# stale-serve here is a delivery-correctness bug, fail fast
python -m pytest tests/test_cache_partition.py -q

echo "== delta-automaton parity + off-lock compaction (docs/DELTA.md) =="
# delta-on vs delta-off exact-match parity under randomized churn,
# bounded route-op latency while a background flatten is in flight,
# and the delta=false legacy pin — a divergence here is a
# match-correctness bug, fail fast
python -m pytest tests/test_delta.py -q

echo "== compressed-walk parity (ops/csr.py compress_automaton) =="
# walk-vs-oracle parity on narrow and wide tables, native-vs-numpy
# chain-fuser parity, and the randomized compressed-walk property
# suite (deep spines, $share, churn, devloss rebuild, checkpoint
# round-trip) — a divergence here is a match-correctness bug in the
# wide-table walk, fail fast
python -m pytest tests/test_compressed_walk.py -q

echo "== flap-storm guard (flapping.py + scenario smoke) =="
python -m pytest tests/test_flapping.py -q

echo "== dispatch planner parity (docs/DISPATCH.md) =="
# planner-on vs legacy per-delivery tail: delivery counts, wire
# bytes, metric deltas must be identical — a divergence here is a
# delivery-correctness bug, fail before the long run
python -m pytest tests/test_dispatch_plan.py -q

echo "== egress pre-serialization parity (docs/DISPATCH.md) =="
# pid-patched template frames vs wire_serialize (independent codec as
# second opinion) + preserialize on/off wire parity — a byte
# divergence here corrupts client streams, fail before the long run
python -m pytest tests/test_egress_serialize.py -q

echo "== multi-loop front-door parity (docs/DISPATCH.md) =="
# loops=1 vs loops=2/4: wire content, pid sequences, delivery counts
# and metric deltas must be identical across the cross-loop delivery
# ring, incl. takeover of a session owned by another loop — a
# divergence here is a delivery-correctness bug, fail fast
python -m pytest tests/test_frontdoor_loops.py -q

echo "== chaos suite (docs/ROBUSTNESS.md) =="
# every registered fault-injection point against the shedding/healing
# behavior it exists to trigger: device failure -> breaker ->
# host-oracle fallback with zero lost deliveries, executor/flatten
# death self-heal, dead-loop will firing, bounded joins, the
# overload-off byte-for-byte pin — a regression here is a
# production-outage bug, fail fast
python -m pytest tests/test_chaos.py -q

echo "== device-loss recovery suite (docs/ROBUSTNESS.md) =="
# the lost-backend rounds specifically (also part of the full suite
# above — re-run focused so a devloss regression is named in CI):
# lost classification -> REBUILDING -> rebuild + rewarm ->
# auto-close with exact deliveries, double loss mid-rebuild, the
# half-open single-probe invariant, host-only fallback, rebuild
# under route churn vs the host oracle, live QoS1 zero-lost/dup
python -m pytest tests/test_chaos.py -q \
    -k "device_lost or device_loss or half_open_single_probe \
or fallback_never or rebuild_under_route or rebuild_off"

echo "== zero-downtime operations: drain + live reload (docs/OPERATIONS.md) =="
# graceful drain (CONNECT gate 0x9C + Server-Reference, paced waves
# with overload-adaptive budget, will suppression, flapping
# exemption, v3.1.1 reconnect-via-registry, digest-verified custody
# hand-off) and the diff-based live config reload (reloadable knobs
# apply atomically, boot-only edits reject whole with a per-knob
# report, classification table lint-checked against the dataclasses)
python -m pytest tests/test_drain.py tests/test_reload.py -q \
    --deselect tests/test_drain.py::test_rolling_restart_3node

echo "== rolling-restart proof (docs/OPERATIONS.md) =="
# the 3-node cluster restarted node-by-node under live durable QoS1
# traffic: zero lost, zero duplicated (sorted(got) == sorted(sent)),
# session custody exactly-one-holder, all five replicated plane
# digests byte-equal after the last rejoin
ROLLING_MSGS=60 python -m pytest \
    tests/test_drain.py::test_rolling_restart_3node -q

echo "== crash recovery (docs/DURABILITY.md) =="
# journal framing/torn-tail/degrade semantics (per shard), the
# kill-point matrix (every armed storage fault x crash stage must
# recover routes / retained / persistent sessions exactly), sharded
# group-commit WAL + order-insensitive merge property, incremental
# checkpoint chains (incl. crash mid-delta), checkpoint-format
# hardening, and the durability-off byte-for-byte pin — a regression
# here is silent data loss after a crash, fail fast
python -m pytest tests/test_wal.py tests/test_durability.py \
    tests/test_checkpoint.py -q

echo "== replicated durability (docs/DURABILITY.md) =="
# journal shipping to the warm standby: ship/ack offsets, standby
# promotion byte-exactness + RPO 0, suspect-aware local-only
# fallback + resync, repl.ship chaos, graceful tail hand-off, and
# the promoted-standby double-recovery pin — a regression here is
# silent data loss at failover, fail fast
python -m pytest tests/test_replication.py -q

echo "== replication groups + failback (docs/DURABILITY.md) =="
# the quorum-grade group story: multi-standby fan-out, the K-1 loss
# survival sweep, bounded quorum waits (ack_quorum=0 async pin),
# deterministic promotion arbitration, the full failover→failback→
# re-failover cycle, crash-during-failback double recovery, and
# promotion under the standby's own live load — a regression here
# is quorum data loss or a split brain, fail fast
python -m pytest tests/test_replication_group.py -q \
    --deselect tests/test_replication_group.py::test_chaos_soak_full

echo "== replication chaos-soak smoke (docs/DURABILITY.md) =="
# the kill-anything scheduler at a fixed seed and bounded rounds:
# the 3-node quorum group takes scripted primary kills (a full
# failover→failback→re-failover cycle) plus randomized node/link
# kills, asserting after every heal that no quorum-acked record is
# lost and every plane digest converges. The driver's real run is
# the 20+-round slow variant (SOAK_ROUNDS)
SOAK_SEED=1337 SOAK_ROUNDS=4 python -m pytest \
    tests/test_replication_group.py::test_chaos_soak_smoke -q

echo "== cluster heal matrix (docs/CLUSTER.md) =="
# failure detector (wedged-peer detection, suspect-parks-not-purges,
# fast-fail + degraded locker quorum), auto-heal + anti-entropy
# (partition/heal convergence of all five replicated planes vs a
# never-partitioned oracle), and the detector-off legacy pin — a
# regression here is silent cluster divergence, fail fast
python -m pytest tests/test_cluster_heal.py -q

echo "== telemetry (docs/OBSERVABILITY.md) =="
# the publish-path telemetry suite, incl. the disabled-mode A/B
# guard (telemetry off => dispatch byte-identical to the
# un-instrumented broker) — run early so an instrumentation
# regression fails fast
python -m pytest tests/test_telemetry.py -q

echo "== tracing + slow_subs (docs/OBSERVABILITY.md \"Tracing\") =="
# end-to-end message tracing: deterministic sampling, the
# sample_rate=0 byte-identity + zero-allocation pin, ring-overflow
# accounting, slow-subscriber ranking/expiry/alarm, cluster-forward
# context carriage, and the loop profiler / profile-stop satellites
python -m pytest tests/test_tracing.py -q

echo "== trace-export smoke (docs/OBSERVABILITY.md) =="
# a sampled publish through a loops=2 node (device matcher, QoS1
# fan-out over the cross-loop ring), exported with `ctl trace
# export`: the Chrome trace JSON must contain a complete
# ingress→match→dispatch→publish→flush chain for a sampled trace id,
# an xloop hop, and flush spans attributed to both subscriber
# clientids — run focused so an export regression is named in CI
python -m pytest \
    tests/test_tracing.py::test_trace_chain_is_continuous_across_two_loops -q

echo "== native frame-parser parity (docs/OBSERVABILITY.md \"Frame parser\") =="
# differential fuzz of the C++ incremental parser vs the Python
# parser vs the independent test codec (parsed packets, error
# classes, buffered remainders, resume at every byte split), the
# read-path allocation-count pins, and the server-level engine-knob
# suite (counters, env override, fallback, oversize 0x95) — a
# divergence here is a wire-corruption bug, fail fast
python -m pytest tests/test_frame_fuzz.py tests/test_frame_zerocopy.py \
    tests/test_frame_native.py -q

echo "== multi-loop parity under the native frame engine =="
# the full front-door loops parity suite re-run with
# EMQX_TPU_FRAME=native: the engine must be invisible to every
# cross-loop delivery/takeover invariant (skips cleanly if the
# native library is not built — make_parser falls back to Python)
EMQX_TPU_FRAME=native python -m pytest tests/test_frontdoor_loops.py -q

echo "== retained replay parity (docs/DISPATCH.md \"Retained replay\") =="
# batched subscribe-time matching vs the T.match host oracle,
# planner on/off + loops=1/2 replay wire parity, the ≤1-wakeup /
# onloop==0 delivery contract, will batching, devloss riding — a
# divergence here is a delivery-correctness bug, fail before the long
# run
python -m pytest tests/test_retained_replay.py -q

echo "== pytest =="
if [[ "${COV:-1}" == "0" ]]; then
    python -m pytest tests -q
else
    echo "(measuring line coverage; COV=0 to skip)"
    python scripts/cov.py --filter emqx_tpu --out COVERAGE.txt -- \
        -m pytest tests -q
    tail -1 COVERAGE.txt
fi

echo "CI gate: OK"
