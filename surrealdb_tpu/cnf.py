"""Environment-variable configuration statics (reference: core/src/cnf/
mod.rs `lazy_env_parse!` knobs — the same SURREAL_* names where the knob
exists in this build)."""

from __future__ import annotations

import os


def env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def env_str(name: str, default: str) -> str:
    return os.environ.get(name, "") or default


def env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name, "").lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off"):
        return False
    return default


# expression/statement nesting depth (ctx chain)
MAX_COMPUTATION_DEPTH = env_int("SURREAL_MAX_COMPUTATION_DEPTH", 120)
# .{..} idiom recursion hard limit
IDIOM_RECURSION_LIMIT = env_int("SURREAL_IDIOM_RECURSION_LIMIT", 256)
# embedded-script op budget
SCRIPTING_MAX_OPS = env_int("SURREAL_SCRIPTING_MAX_OPS", 2_000_000)
# write-side batching of the vector-index op log before a full repack
INDEXING_BATCH_SIZE = env_int("SURREAL_INDEXING_BATCH_SIZE", 250)
# device KNN thresholds
KNN_DEVICE_MIN_ROWS = env_int("SURREAL_KNN_DEVICE_MIN_ROWS", 2048)
KNN_BLOCK_ROWS = env_int("SURREAL_KNN_BLOCK_ROWS", 262144)
# query-batch chunk per lax.map step in the ranking kernel (MXU batch dim)
KNN_QUERY_CHUNK = env_int("SURREAL_KNN_QUERY_CHUNK", 512)
# peak [chunk, N] f32 score-matrix elements per ranking step (~2 GB HBM);
# large stores shrink the per-step query chunk to stay under this
KNN_SCORE_BUDGET_ELEMS = env_int(
    "SURREAL_KNN_SCORE_BUDGET_ELEMS", 1 << 29
)
# device HBM budget for the KNN stores (bytes). When bf16-rank + f32-full
# (6 B/elem) would exceed it, the index switches to the int8 ranking store
# (1 B/elem) + host-side exact rescore — the 10M×768 regime on a 16 GB v5e
KNN_HBM_BUDGET_BYTES = env_int(
    "SURREAL_KNN_HBM_BUDGET_BYTES", 12 << 30
)
# candidate oversampling multiple (×k) for the int8 ranking store; higher
# absorbs quantization error before the exact host rescore
KNN_INT8_OVERSAMPLE = env_int("SURREAL_KNN_INT8_OVERSAMPLE", 128)
# -- quantized graph-ANN index (idx/cagra.py, device/annstore.py) ------------
# auto: stores at/above ANN_MIN_ROWS with an MXU metric build a CAGRA-
# style fixed-degree graph in the background and route <|k|> searches
# through int8 greedy descent + exact re-rank once it is ready (brute
# force serves until then). off: never. force: build for any store
# above a small floor (tests/benches).
KNN_ANN_MODE = env_str("SURREAL_KNN_ANN", "auto")
KNN_ANN_MIN_ROWS = env_int("SURREAL_KNN_ANN_MIN_ROWS", 200_000)
# fixed out-degree of the search graph ([N, D_out] int32)
KNN_ANN_DEGREE = env_int("SURREAL_KNN_ANN_DEGREE", 32)
# greedy-descent frontier width (itopk); rounded up to a power of two
# and never below the re-rank candidate count
KNN_ANN_SEARCH_WIDTH = env_int("SURREAL_KNN_ANN_SEARCH_WIDTH", 64)
# fixed descent iterations / nodes expanded per iteration (static
# shapes: the compiled kernel ladder stays bounded)
KNN_ANN_ITERS = env_int("SURREAL_KNN_ANN_ITERS", 24)
KNN_ANN_EXPAND = env_int("SURREAL_KNN_ANN_EXPAND", 2)
# exact re-rank oversampling: kc = max(OVERSAMPLE * k, 32) candidates
# leave the descent and are re-scored from the f32 host rows
KNN_ANN_OVERSAMPLE = env_int("SURREAL_KNN_ANN_OVERSAMPLE", 4)
# routing-probe floor (strided rows brute-scored to seed the descent);
# covers clusters the fixed graph entries can't route to — one
# [B, probe] gemm per batch, ≪ a brute scan while probe ≪ N
KNN_ANN_PROBE = env_int("SURREAL_KNN_ANN_PROBE", 4096)
# ...and its size as a fraction of N: a FIXED probe's cluster-miss rate
# grows with the store (a cluster of s rows is missed with p≈e^(-P·s/N),
# so at constant P and cluster size, recall decays as N grows —
# measured 0.97 at 100k → 0.80 at 250k with P=4096). A constant
# FRACTION pins the per-cluster expectation: P = N/24 keeps the miss
# rate ≈ e^(-4) for 100-row clusters at any N, at ~4% of a brute
# scan's per-query cost.
KNN_ANN_PROBE_FRAC = env_float("SURREAL_KNN_ANN_PROBE_FRAC", 1 / 24)
# k above which the planner keeps brute force (descent width economics)
KNN_ANN_MAX_K = env_int("SURREAL_KNN_ANN_MAX_K", 64)
# build knobs: RP-partition leaf size (exact kNN within a leaf), number
# of trees merged, NN-descent refine rounds (-1 = auto: 1 round up to
# 200k rows, 0 above — the gather traffic dominates at multi-million N)
KNN_ANN_LEAF = env_int("SURREAL_KNN_ANN_LEAF", 512)
KNN_ANN_TREES = env_int("SURREAL_KNN_ANN_TREES", 2)
KNN_ANN_REFINE = env_int("SURREAL_KNN_ANN_REFINE", -1)
# int8 quantization clip quantile (density-aware: per-row scale from
# this |x| quantile instead of the max, so one outlier coordinate
# cannot crush the row's resolution). Default 1.0 = exact max: on
# near-gaussian rows (normalized embeddings) a sub-max clip SATURATES
# the largest coordinates, and that bias costs more recall than the
# resolution buys (measured: cosine recall@10 0.86 → 1.00 at kc=4k).
# Lower it only for stores with genuine heavy-tailed outlier dims.
KNN_ANN_CLIP_Q = env_float("SURREAL_KNN_ANN_CLIP_Q", 1.0)
# appended-tail tolerance: rows written after the graph was built are
# brute-ranked and merged into the re-rank set; past this fraction the
# graph is considered stale and a rebuild is scheduled
KNN_ANN_TAIL_FRAC = env_float("SURREAL_KNN_ANN_TAIL_FRAC", 0.25)

# -- segmented LSM-style ANN (idx/segments.py) -------------------------------
# Sealed-segment serving for continuous ingest: writes land in a small
# mutable exact segment, a seal policy freezes it, background jobs
# build per-segment CAGRA graphs and tier-merge small segments into
# larger ones — the whole-index rebuild treadmill (KNN_ANN_TAIL_FRAC)
# never runs. auto: engage once the store crosses KNN_SEG_MIN_ROWS
# (the legacy single-graph path serves smaller stores unchanged).
# off: never. force: engage at a tiny floor (tests/benches).
KNN_SEG_MODE = env_str("SURREAL_KNN_SEG", "auto")
KNN_SEG_MIN_ROWS = env_int("SURREAL_KNN_SEG_MIN_ROWS", 400_000)
# seal policy for the mutable tail: row count, byte size, or age (the
# age seal is clockless by default — 0 disables it — so the
# deterministic sim replays; it is checked at sync cadence, no timers)
KNN_SEG_ROWS = env_int("SURREAL_KNN_SEG_ROWS", 131_072)
KNN_SEG_BYTES = env_int("SURREAL_KNN_SEG_BYTES", 512 << 20)
KNN_SEG_AGE_S = env_float("SURREAL_KNN_SEG_AGE_S", 0.0)
# tiered merge policy: when this many adjacent sealed segments share a
# size tier (tier t covers [SEG_ROWS * FANOUT^t, SEG_ROWS *
# FANOUT^(t+1)) live rows), a background job compacts them into one —
# LSM geometric tiers, so per-row (re)build work stays O(log n) and
# merge compaction is where tombstoned rows finally leave a graph
KNN_SEG_FANOUT = env_int("SURREAL_KNN_SEG_FANOUT", 4)
# per-segment tombstone/overwrite fraction past which the SEGMENT's
# graph is rebuilt (compacting its dead rows out) — segment-local
# staleness replaces the global drift threshold entirely
KNN_SEG_TOMB_FRAC = env_float("SURREAL_KNN_SEG_TOMB_FRAC", 0.5)

# scoring-path routing for the cross-query batcher (idx/vector.py):
#   auto   — dispatch to the device runner on real accelerators; when the
#            "device" IS the host CPU (platform cpu), score from the
#            batched BLAS host path instead (offloading numpy-speed
#            kernels through jax only adds dispatch overhead)
#   device — always dispatch to the device when it is serving
#   host   — always score on the host (batched)
KNN_HOST_BATCH = env_str("SURREAL_KNN_HOST_BATCH", "auto")

# -- shard-partitioned vector serving (idx/shardvec.py) ---------------------
# partial-result policy when a shard cannot serve its slice of a KNN
# query within budget:
#   error   — the query fails with a typed error naming the shard (safe
#             default: an application that never opted in can never act
#             on a silently incomplete candidate set)
#   partial — answer from the healthy shards, flagged in the response
#             (QueryResult.partial names every missing shard) and
#             counted (knn_partial_results) — never silently wrong
KNN_PARTIAL = env_str("SURREAL_KNN_PARTIAL", "error")
# per-shard budget (seconds) carved from the query's remaining inflight
# deadline for one scatter attempt (sync + per-shard search); a sick
# shard can burn at most this much of the query, not the whole budget
KNN_SHARD_TIMEOUT_S = env_float("SURREAL_KNN_SHARD_TIMEOUT_S", 1.5)
# bounded hedged retry: after the first scatter round, every failed
# shard gets at most this many re-dispatches (through the group's
# failover-following pool, against a refreshed shard map) before the
# partial policy applies. 0 disables hedging.
KNN_SHARD_HEDGES = env_int("SURREAL_KNN_SHARD_HEDGES", 1)
# per-shard fetch multiplier: each shard answers ceil(k * oversample)
# candidates. Exact (brute) parts need only 1.0 for an exact global
# top-k; raising it buys recall when a part serves from its CAGRA
# graph (see doc/operations.md "Distributed vector serving")
KNN_SHARD_OVERSAMPLE = env_float("SURREAL_KNN_SHARD_OVERSAMPLE", 1.0)
# scatter execution:
#   auto    — per-shard SYNC attempts fan out across worker threads on
#             real transports (they park on remote I/O, so threads
#             genuinely overlap), sequential under an injected
#             transport (the deterministic simulator owns all
#             interleaving); local per-part searches stay sequential
#             (GIL-bound: a straight loop beats thread fan-out)
#   threads — also fan local searches out (many-core hosts)
#   seq     — everything sequential
KNN_SCATTER = env_str("SURREAL_KNN_SCATTER", "auto")
# content-keyed value-decode cache (bytes); identical stored bytes skip
# CBOR re-decode on repeated scans. 0 disables.
DECODE_CACHE_BYTES = env_int("SURREAL_DECODE_CACHE_BYTES", 256 << 20)
# parsed-statement cache entries (Datastore.execute)
AST_CACHE_SIZE = env_int("SURREAL_AST_CACHE_SIZE", 512)
# slow-query log threshold (ms); 0 disables
SLOW_QUERY_THRESHOLD_MS = env_float("SURREAL_SLOW_QUERY_THRESHOLD_MS", 0.0)
# file-engine WAL batches between snapshot compactions
WAL_COMPACT_BATCHES = env_int("SURREAL_WAL_COMPACT_BATCHES", 4096)

# LSM engine (kvs/lsm.py — reference surrealkv role)
LSM_MEMTABLE_BYTES = env_int("SURREAL_LSM_MEMTABLE_BYTES", 8 << 20)
LSM_COMPACT_SEGMENTS = env_int("SURREAL_LSM_COMPACT_SEGMENTS", 6)

# memory kill-switch (reference core/src/mem + cnf MEMORY_THRESHOLD;
# 0 disables, any other value floors at 1 MiB)
MEMORY_THRESHOLD = env_int("SURREAL_MEMORY_THRESHOLD", 0)

# -- node-wide resource governance (resource.py) -----------------------------
# node budget for accounted derived state (vector stores, ANN graphs,
# FT cache, CSR blocks, outboxes, ...). 0 = auto: MEM_BUDGET_FRAC of
# the cgroup/host memory limit. Crossing budget*MEM_SOFT_FRAC triggers
# priority-ordered eviction; crossing the budget (hard watermark)
# sheds new admissions with a typed 503 and pauses allocation-heavy
# builds at their chunk boundaries. These are read at accountant
# construction / set_budget time (env_... at call), not import time.
MEM_BUDGET_MB = env_int("SURREAL_MEM_BUDGET_MB", 0)
MEM_BUDGET_FRAC = env_float("SURREAL_MEM_BUDGET_FRAC", 0.5)
MEM_SOFT_FRAC = env_float("SURREAL_MEM_SOFT_FRAC", 0.8)
# bounded wait at a build chunk boundary while the node stays over the
# hard watermark (0 = evict-and-continue; keeps the simulator clockless)
MEM_PAUSE_S = env_float("SURREAL_MEM_PAUSE_S", 0.0)
# full-text result cache bounds (idx/fulltext.py FtResult entries):
# entry count + estimated bytes, LRU-evicted (ft_cache_evictions)
FT_CACHE_ENTRIES = env_int("SURREAL_FT_CACHE_ENTRIES", 512)
FT_CACHE_BYTES = env_int("SURREAL_FT_CACHE_BYTES", 64 << 20)
# device-runner store budget (device/handlers.py): total device-resident
# bytes across vec/ann/csr block caches + multipart staging. 0 disables
# byte budgeting (the per-kind LRU entry caps still bound the caches).
# An admission evicts LRU stores first (eviction = re-ship, never an
# error); a store that cannot fit even an empty runner is REFUSED with
# a typed DeviceOutOfMemory and serves from host paths instead.
DEVICE_MEM_BUDGET_MB = env_int("SURREAL_DEVICE_MEM_BUDGET_MB", 0)

# -- remote KV client: retry / backoff / failover (kvs/remote.py) ------------
# total deadline for one logical KV operation across retries+failover
KV_RETRY_DEADLINE_S = env_float("SURREAL_KV_RETRY_DEADLINE_S", 15.0)
# exponential-backoff schedule: base * 2^attempt, capped at max, with
# full jitter in [1-KV_RETRY_JITTER, 1] of the computed delay
KV_RETRY_BASE_MS = env_float("SURREAL_KV_RETRY_BASE_MS", 25.0)
KV_RETRY_MAX_MS = env_float("SURREAL_KV_RETRY_MAX_MS", 1000.0)
KV_RETRY_JITTER = env_float("SURREAL_KV_RETRY_JITTER", 0.5)
# per-call socket timeout (a partition must not stall a client forever)
KV_OP_TIMEOUT_S = env_float("SURREAL_KV_OP_TIMEOUT_S", 30.0)
KV_CONNECT_TIMEOUT_S = env_float("SURREAL_KV_CONNECT_TIMEOUT_S", 5.0)

# -- remote KV service: replication / failover (kvs/remote.py, node.py) ------
# primary-lease TTL; the primary renews at TTL/3 through the replicated
# keyspace, so replicas observe liveness via the lease row itself
KV_LEASE_TTL_S = env_float("SURREAL_KV_LEASE_TTL_S", 6.0)
# how long a replica waits without replication traffic before it starts
# the promotion protocol (lease check -> peer survey -> self-promote)
KV_FAILOVER_TIMEOUT_S = env_float("SURREAL_KV_FAILOVER_TIMEOUT_S", 8.0)

# -- follower reads: closed-timestamp bounded staleness (kvs/remote.py) ------
# a read-only transaction carrying a max_staleness bound (READ AT in
# SQL) may be served by a REPLICA that can prove the requested
# timestamp is closed: the primary publishes a monotone closed
# timestamp in every repl frame and on the heartbeat cadence, so a
# replica's lag is bounded even when writes pause. 0/None-bounded
# (default, exact) reads stay primary-served and byte-identical.
KV_FOLLOWER_READS = env_str("SURREAL_KV_FOLLOWER_READS", "on")
# mutation-test hook (sim/harness.py): True bypasses the replica-side
# closed-timestamp proof so the DST follower-read invariant can prove
# it BITES — never set outside a mutation test.
KV_FOLLOWER_PROOF_DISABLED = False

# -- range sharding / cross-shard 2PC (kvs/shard.py, kvs/remote.py) ----------
# versionstamps for a sharded store come in windows leased from the meta
# shard (PD-style TSO): one meta round-trip hands out this many stamps.
# A leased window EXPIRES after the TTL: an idle node discards its
# remainder and re-leases, which bounds how stale a stamp can be
# relative to other nodes' commits (a changefeed cursor that advanced
# past an abandoned window must not see older stamps appear later).
KV_TSO_WINDOW = env_int("SURREAL_KV_TSO_WINDOW", 512)
KV_TSO_WINDOW_TTL_S = env_float("SURREAL_KV_TSO_WINDOW_TTL_S", 5.0)
# a staged prepare whose coordinator has been silent this long is an
# orphan: the participant resolves it through the meta commit log,
# claiming abort if no decision was recorded
KV_2PC_ORPHAN_GRACE_S = env_float("SURREAL_KV_2PC_ORPHAN_GRACE_S", 5.0)
KV_2PC_RESOLVE_INTERVAL_S = env_float(
    "SURREAL_KV_2PC_RESOLVE_INTERVAL_S", 0.5
)

# -- accelerator backend init watchdog (device supervisor) -------------------
# a runner whose device discovery exceeds this is killed: the serving
# path degrades to host execution (auto) or fails the query (require)
BACKEND_INIT_TIMEOUT_S = env_float("SURREAL_BACKEND_INIT_TIMEOUT_S", 240.0)

# -- device execution supervisor (device/supervisor.py) ----------------------
# off: host paths only. auto (default): supervised DeviceRunner
# subprocess, degrade-and-recover. require: device failures surface as
# query errors instead of silently degrading. inline: run device ops
# in-process (debug/tests — forfeits fault isolation).
DEVICE_MODE = env_str("SURREAL_DEVICE", "auto")
# mesh execution (device/mesh.py): row-shard vec/ANN/CSR blocks across
# jax.devices() with on-mesh partial top-k + exact merge. auto
# (default): shard only when a store's single-device share busts the
# per-device byte budget. off: legacy single-device stores. force:
# always shard across the full mesh. An integer caps the mesh width.
# Read per-call (os.environ first) so tests/bench can flip it without
# a cnf reload.
DEVICE_MESH = env_str("SURREAL_DEVICE_MESH", "auto")
# per-dispatch deadline; a dispatch that exhausts the FULL window is a
# wedge (runner SIGKILLed + circuit opens). Also capped per call by the
# query's remaining budget (inflight.remaining()).
DEVICE_DISPATCH_TIMEOUT_S = env_float("SURREAL_DEVICE_DISPATCH_TIMEOUT_S",
                                      10.0)
# block-cache ship deadline (whole stores cross the socketpair)
DEVICE_LOAD_TIMEOUT_S = env_float("SURREAL_DEVICE_LOAD_TIMEOUT_S", 120.0)
# degraded-state background re-probe cadence + promotion hysteresis
# (consecutive healthy probes required before traffic returns)
DEVICE_PROBE_INTERVAL_S = env_float("SURREAL_DEVICE_PROBE_INTERVAL_S", 5.0)
DEVICE_PROMOTE_SUCCESSES = env_int("SURREAL_DEVICE_PROMOTE_SUCCESSES", 2)
# cross-query batcher dispatch pipelining (device/batcher.py): up to
# PIPELINE dispatches in flight at once — a second batch may launch
# while the first is inside its kernel (GIL released), keeping the
# scoring kernel busy while query threads run their Python halves.
# The overlapped dispatch only launches once PIPELINE_MIN riders are
# queued, so light traffic keeps the strict one-batch-at-a-time
# coalescing (maximum batch growth, no dribble dispatches).
DEVICE_BATCH_PIPELINE = env_int("SURREAL_DEVICE_BATCH_PIPELINE", 2)
DEVICE_BATCH_PIPELINE_MIN = env_int("SURREAL_DEVICE_BATCH_PIPELINE_MIN",
                                    32)
# power-of-two query-bucket ladder pre-warmed right after a vec store
# ships to the runner ("" disables). With the persistent compile cache
# warm these are near-free; cold, they front-load the XLA compiles so
# serving traffic never pays one mid-query.
DEVICE_PREWARM_BUCKETS = env_str("SURREAL_DEVICE_PREWARM_BUCKETS",
                                 "1,8,64")
# hop depths pre-compiled after a CSR graph ships (same rationale as
# the bucket ladder: the first multi-hop after a ship/restart must not
# pay an XLA compile mid-query); "" disables
DEVICE_PREWARM_HOPS = env_str("SURREAL_DEVICE_PREWARM_HOPS", "1,2,3")

# -- admission control / query lifecycle (server/admission.py, inflight.py) --
# concurrent queries executing at once (the worker-slot budget); the CLI
# --max-inflight flag overrides. 0 disables admission control entirely.
HTTP_MAX_INFLIGHT = env_int("SURREAL_HTTP_MAX_INFLIGHT", 64)
# requests allowed to WAIT for a slot; one past this sheds with a 503
HTTP_QUEUE_DEPTH = env_int("SURREAL_HTTP_QUEUE_DEPTH", 128)
# server-side default query timeout seeding ExecContext.deadline when the
# client sends no X-Surreal-Timeout / rpc timeout field (0 = unbounded)
HTTP_DEFAULT_TIMEOUT_S = env_float("SURREAL_HTTP_DEFAULT_TIMEOUT_S", 0.0)
# SIGTERM drain budget: stop admitting, let in-flight work finish this
# long, then cancel whatever remains and exit
DRAIN_TIMEOUT_S = env_float("SURREAL_DRAIN_TIMEOUT_S", 10.0)


# -- live-query fan-out (server/fanout.py) -----------------------------------
# per-session bounded outbound notification queue: the writer thread
# drains it toward the client socket; a full queue triggers the
# overflow policy instead of ever blocking a committing writer
LIVE_QUEUE_DEPTH = env_int("SURREAL_LIVE_QUEUE_DEPTH", 256)
# what happens to a slow consumer whose queue overflows:
#   notify     — drop the queued backlog, count it, and push one typed
#                OVERFLOW notification per bound live id (the client
#                knows it lost a window and can re-read)
#   disconnect — force-close the laggard's connection (the client's
#                reconnect logic owns recovery)
LIVE_OVERFLOW_POLICY = env_str("SURREAL_LIVE_OVERFLOW", "notify")
# post-commit dispatch workers doing live-query matching (condition +
# projection evaluation). Events are sharded by (ns,db,tb) so one
# subscription always observes its table's commits in order.
LIVE_DISPATCH_WORKERS = env_int("SURREAL_LIVE_DISPATCH_WORKERS", 2)
# commit batches a dispatch worker may have queued before the hub
# declares push overload: the backlog is dropped and every subscription
# on the affected tables gets a typed OVERFLOW notification (bounded
# memory under a notification storm, honestly reported)
LIVE_DISPATCH_BACKLOG = env_int("SURREAL_LIVE_DISPATCH_BACKLOG", 4096)
# notifications coalesced into one socket write by a session's writer
# thread (burst batching: N frames, one sendall)
LIVE_DELIVERY_BATCH = env_int("SURREAL_LIVE_DELIVERY_BATCH", 64)
# dead-session sweep cadence (rides the kvs/net.py Runtime seam): GC
# live queries whose session died without KILL
LIVE_SWEEP_INTERVAL_S = env_float("SURREAL_LIVE_SWEEP_INTERVAL_S", 30.0)
# embedded in-process notification buffer cap (Datastore.notifications —
# drained by drain_notifications(); without a consumer it must not grow
# without bound). Drops are counted; first drop warns once.
NOTIFY_BUFFER_CAP = env_int("SURREAL_NOTIFY_BUFFER_CAP", 10_000)

# -- changefeed GC (cf.py, scheduled by the serving path) --------------------
# fallback retention for tables/databases whose CHANGEFEED clause
# carries no duration this build can read (seconds); per-table clauses
# always win. 0 disables the sweep entirely.
CHANGEFEED_RETENTION_S = env_float("SURREAL_CHANGEFEED_RETENTION_S",
                                   3 * 86400.0)
CHANGEFEED_GC_INTERVAL_S = env_float("SURREAL_CHANGEFEED_GC_INTERVAL_S",
                                     300.0)

# -- execution limits (reference cnf/mod.rs names) ---------------------------
# rows buffered per streaming operator batch (OPERATOR_BUFFER_SIZE)
OPERATOR_BUFFER_SIZE = env_int("SURREAL_OPERATOR_BUFFER_SIZE", 1024)
# columnar executor (exec/batch.py + exec/vops.py): "auto" engages the
# vectorized predicate/aggregate kernels and the version-keyed table
# column store; "off" forces every row through the scalar evaluator —
# the conformance fallback-correctness gate diffs the two paths
COLUMNAR = env_str("SURREAL_COLUMNAR", "auto")
# seeded RNG for ORDER BY RAND / array::shuffle-style statement paths:
# 0 = OS entropy (production default); a non-zero seed makes sim/bench
# runs reproducible (the RNG is datastore-scoped, never `random`'s
# process-global instance)
RAND_SEED = env_int("SURREAL_RAND_SEED", 0)
# concurrent tasks in fan-out sections (MAX_CONCURRENT_TASKS)
MAX_CONCURRENT_TASKS = env_int("SURREAL_MAX_CONCURRENT_TASKS", 64)
# statements per query text (guards pathological batches)
MAX_STATEMENTS_PER_QUERY = env_int("SURREAL_MAX_STATEMENTS_PER_QUERY", 5000)
# object/array nesting accepted by the parser (MAX_OBJECT_PARSING_DEPTH /
# MAX_QUERY_PARSING_DEPTH)
MAX_OBJECT_PARSING_DEPTH = env_int("SURREAL_MAX_OBJECT_PARSING_DEPTH", 100)
MAX_QUERY_PARSING_DEPTH = env_int("SURREAL_MAX_QUERY_PARSING_DEPTH", 100)
# generated-collection byte cap (GENERATION_ALLOCATION_LIMIT: 2^n bytes)
GENERATION_ALLOCATION_LIMIT = 2 ** min(
    env_int("SURREAL_GENERATION_ALLOCATION_LIMIT", 20), 28
)
# similarity/distance function input cap (FUNCTION_SIMILARITY_MAX_LENGTH)
FUNCTION_SIMILARITY_MAX_LENGTH = env_int(
    "SURREAL_FUNCTION_SIMILARITY_MAX_LENGTH", 100_000
)
# regex compile cache + size cap (REGEX_CACHE_SIZE / REGEX_SIZE_LIMIT)
REGEX_CACHE_SIZE = env_int("SURREAL_REGEX_CACHE_SIZE", 1000)
REGEX_SIZE_LIMIT = env_int("SURREAL_REGEX_SIZE_LIMIT", 10_485_760)

# -- transactions / datastore ------------------------------------------------
# max keys per external scan batch (MAX_BATCH_SIZE / EXPORT_BATCH_SIZE)
MAX_BATCH_SIZE = env_int("SURREAL_MAX_BATCH_SIZE", 10_000)
EXPORT_BATCH_SIZE = env_int("SURREAL_EXPORT_BATCH_SIZE", 1000)
# transaction-level catalog/record cache entries (kvs/tx.rs caches)
TRANSACTION_CACHE_SIZE = env_int("SURREAL_TRANSACTION_CACHE_SIZE", 10_000)
# datastore-level cross-txn cache entries (DatastoreCache)
DATASTORE_CACHE_SIZE = env_int("SURREAL_DATASTORE_CACHE_SIZE", 1000)
# changefeed GC: retain at most this many versionstamped entries per table
CHANGEFEED_GC_BATCH_SIZE = env_int("SURREAL_CHANGEFEED_GC_BATCH_SIZE", 1000)
# node heartbeat cadence / liveness window (dbs/node.rs tasks)
NODE_MEMBERSHIP_REFRESH_INTERVAL = env_int(
    "SURREAL_NODE_MEMBERSHIP_REFRESH_INTERVAL", 3
)
NODE_MEMBERSHIP_CHECK_INTERVAL = env_int(
    "SURREAL_NODE_MEMBERSHIP_CHECK_INTERVAL", 15
)
# WebSocket / HTTP body caps (server cnf)
WEBSOCKET_MAX_MESSAGE_SIZE = env_int(
    "SURREAL_WEBSOCKET_MAX_MESSAGE_SIZE", 128 << 20
)
HTTP_MAX_BODY_SIZE = env_int("SURREAL_HTTP_MAX_BODY_SIZE", 128 << 20)
# runtime worker threads for the blocking pool (threadpool.rs role)
RUNTIME_WORKER_THREADS = env_int("SURREAL_RUNTIME_WORKER_THREADS", 32)
# bucket (object storage) folder allowlist / global readonly
BUCKET_FOLDER_ALLOWLIST = env_str("SURREAL_BUCKET_FOLDER_ALLOWLIST", "")
GLOBAL_BUCKET_ENFORCED = env_bool("SURREAL_GLOBAL_BUCKET_ENFORCED", False)
# insecure-forward-access-errors (iam verify diagnostics)
INSECURE_FORWARD_ACCESS_ERRORS = env_bool(
    "SURREAL_INSECURE_FORWARD_ACCESS_ERRORS", False
)
# surrealism host imports: allow modules to run SurrealQL via the
# `sdb.sql` host function (runs under the calling session's permissions)
SURREALISM_HOST_SQL = env_bool("SURREAL_SURREALISM_HOST_SQL", True)
