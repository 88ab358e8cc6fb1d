"""Deterministic cluster simulation: seed corpus + reproducibility +
invariant-checker sensitivity (mutation test).

The corpus seeds run the FULL acceptance-shape cluster (meta + 3 data
shards, each 1 primary + 2 replicas, plus a spare split-target group,
8 simulated clients) under the seeded crash / partition / latency /
drop / split schedule in virtual time. Seeds that once exposed real
bugs are pinned here forever:

- seed 2  — found the check-then-act race: a 2PC prepare staged on a
  node that demoted between the dispatch role check and wal_lock
  (half-applied cross-shard commit), and the stale-replica read hole
  (a fresh client pool serving reads from a demoted replica).
- seed 13 — found that in-memory applied_seq is not a valid election
  freshness metric across restarts (acked writes resynced away by a
  stale winner) — now ranked by the durable (era, seq) credential.
- seed 22 — found the quiesce knob-reset race in the harness and the
  split-retry availability hole.

The broad randomized sweep (200 seeds) runs under `-m slow`.
"""

import pytest

from surrealdb_tpu.sim import SimConfig, run_sim

# known-interesting + spread seeds; tier-1 runs all of them in virtual
# time (the whole corpus takes well under a minute of real time)
CORPUS = [0, 1, 2, 3, 5, 7, 11, 13, 17, 19, 22, 23, 29, 31, 37, 41,
          55, 77, 101, 137]


@pytest.fixture(autouse=True)
def collect_between_simulations():
    """A transaction that a finished simulation left open cancels
    itself when it is collected (`kvs/shard.py ShardTx.__del__`), over
    the simulated network. Collected in the middle of the NEXT
    simulation's scheduling step, that finalizer waits for the kernel's
    mutex in the thread that holds it, and the seed ends at its 300 s
    wall-clock watchdog: the flake of `test_seed_corpus[19]` in whole
    runs (PR 33 caught its stack). What one simulation leaves behind
    is collected here, before the next begins."""
    import gc

    gc.collect()
    yield


def _small():
    return SimConfig(groups=2, members=3, spare_groups=0, clients=4,
                     ops_per_client=10, splits=0)


@pytest.mark.parametrize("seed", CORPUS)
def test_seed_corpus(seed):
    res = run_sim(seed)
    assert res.ok, (
        f"seed {seed}: violations={res.violations[:4]} "
        f"errors={res.errors[:2]} — replay with "
        f"`python tools/sim_explore.py --seed {seed} -v`"
    )
    # chaos actually ran: frames flowed and ops completed
    assert res.stats["acked"] > 0
    assert res.stats["frames"] > 100


def test_bit_reproducible_same_seed():
    """Same seed => same event trace and same final store digest,
    across two independent invocations (the acceptance criterion that
    makes any failure replayable)."""
    a = run_sim(77)
    b = run_sim(77)
    assert a.trace_digest == b.trace_digest
    assert a.store_digest == b.store_digest
    assert a.virtual_s == b.virtual_s
    assert a.stats["events"] == b.stats["events"]
    # and a different seed explores a different universe
    c = run_sim(78)
    assert c.trace_digest != a.trace_digest


def test_virtual_time_is_fast():
    """A multi-second failover scenario must not sleep for real."""
    import time

    t0 = time.monotonic()
    res = run_sim(5, _small())
    real = time.monotonic() - t0
    assert res.virtual_s > 10.0
    assert real < res.virtual_s / 3, (
        f"virtual time is not virtual: {real:.1f}s real for "
        f"{res.virtual_s:.1f}s virtual"
    )


def _partition_primary_schedule():
    """Scripted: cut group 1's boot primary off from both replicas for
    a long window, then heal. Clients still reach every node."""
    return SimConfig(
        groups=2, members=3, spare_groups=0, clients=2,
        ops_per_client=8, splits=0,
        scripted_faults=[
            (3.0, "partition", "g1m0", "g1m1", "both"),
            (3.0, "partition", "g1m0", "g1m2", "both"),
            (22.0, "heal"),
        ],
    )


def test_partitioned_primary_steps_down_clean():
    """Baseline for the mutation test: with the REAL protocol, the
    partitioned primary steps down, a replica promotes, and every
    invariant holds after healing."""
    res = run_sim(7, _partition_primary_schedule())
    assert res.ok, (res.violations[:4], res.errors[:2])
    joined = "\n".join(res.trace)
    assert "ev=promote" in joined
    assert "ev=demote" in joined


def test_lease_mutation_caught_by_invariant(monkeypatch):
    """Mutation test: break the lease protocol on purpose — the old
    primary neither refuses unreplicated writes nor steps down when its
    lease expires — and the lease-safety invariant must catch the two
    concurrent primaries. Proves the checker has teeth."""
    from surrealdb_tpu.kvs.remote import KvEngine

    monkeypatch.setattr(KvEngine, "demote",
                        lambda self, reason="admin": None)
    monkeypatch.setattr(KvEngine, "_needs_replica", lambda self: False)
    res = run_sim(7, _partition_primary_schedule())
    assert not res.ok, "broken lease renewal was not detected"
    assert any("LEASE SAFETY" in v or "ACKED" in v or "2PC" in v
               for v in res.violations), res.violations[:6]


def test_asymmetric_partition_heals_in_sim():
    """One-way cut: the primary's frames to its replicas vanish but
    the reverse direction flows. Failover + heal must converge with
    all invariants green (the sim half of the kvs/faults.py asymmetric
    partition satellite)."""
    cfg = SimConfig(
        groups=2, members=3, spare_groups=0, clients=2,
        ops_per_client=8, splits=0,
        scripted_faults=[
            (3.0, "partition", "g1m0", "g1m1", "a2b"),
            (3.0, "partition", "g1m0", "g1m2", "a2b"),
            (22.0, "heal"),
        ],
    )
    res = run_sim(11, cfg)
    assert res.ok, (res.violations[:4], res.errors[:2])
    assert "ev=promote" in "\n".join(res.trace)


def test_follower_reads_exercised_and_bit_reproducible():
    """The follower-read workload runs inside the chaos sim (replicas
    actually serve), and the observation log is a pure function of the
    seed — any staleness violation is replayable."""
    a = run_sim(7)
    assert a.ok, (a.violations[:4], a.errors[:2])
    assert a.stats["follower_reads"] > 0
    assert a.stats["follower_served"] > 0, (
        "no replica ever served a follower read — the sweep is "
        "proving the fallback path, not the protocol"
    )
    b = run_sim(7)
    assert a.follower_log == b.follower_log
    assert a.trace_digest == b.trace_digest


def test_follower_lag_scenario_rejects_stale_replica():
    """Scripted closed-timestamp scenario: a replica partitioned from
    the primary cannot prove the bound once acked writes outlive it —
    it rejects typed, the healthy replica serves, every observation is
    exact."""
    from surrealdb_tpu.sim.harness import run_follower_lag_sim

    res = run_follower_lag_sim(31337)
    assert res.ok, (res.violations[:4], res.errors[:2])
    assert res.stats["rejected_by"]["g0m1"] > 0, (
        "the frozen replica never rejected — the proof was not "
        "exercised"
    )
    assert res.stats["served_by"]["g0m1"] == 0
    assert res.stats["served_by"]["g0m2"] > 0
    got = {k: g for _s, k, g, _r in res.follower_log}
    assert got == {b"/k/old": b"v-old", b"/k/new": b"v-new"}


def test_follower_proof_mutation_caught_by_invariant():
    """Mutation test: disable the closed-timestamp check
    (cnf.KV_FOLLOWER_PROOF_DISABLED) — the frozen replica now serves
    its stale prefix and check_follower_reads MUST flag the
    beyond-bound answer. Proves the invariant has teeth."""
    from surrealdb_tpu.sim.harness import run_follower_lag_sim

    res = run_follower_lag_sim(31337, proof_disabled=True)
    assert not res.ok, "the disabled proof went undetected"
    assert any("FOLLOWER STALE BEYOND BOUND" in v
               for v in res.violations), res.violations[:4]


@pytest.mark.slow
def test_randomized_sweep_200_seeds():
    """The broad sweep: 200 random seeds of full-config chaos, every
    invariant green on each."""
    fails = []
    for seed in range(1000, 1200):
        res = run_sim(seed)
        if not res.ok:
            fails.append((seed, res.violations[:3], res.errors[:2]))
    assert not fails, f"{len(fails)} failing seeds: {fails[:5]}"


# ---------------------------------------------------------------------------
# index-serving simulation (scatter-gather KNN, idx/shardvec.py)
# ---------------------------------------------------------------------------
# The KNN sim mounts a REAL Datastore (executor + planner + sharded
# vector router) on the simulated cluster: KNN queries race writes,
# online splits through the element keyspace, primary kills, and
# asymmetric partitions, under SURREAL_KNN_PARTIAL=partial. The
# check_knn_delivery invariant holds every answer to: non-partial ==
# brute-force oracle over acked rows (exact distances, zero silent
# loss), partial == typed and naming the missing shard. Seeds chosen
# for behavioral spread: 0 (partial + typed errors + split), 3
# (multi-partial + errors + split), 4 (clean run — the oracle must
# also hold with no faults landing), 8 (partial + error, no split),
# 14 (all three). The development sweeps (80 + 60 seeds) found no
# delivery violations; the mutation test below proves the checker
# would have seen them.

KNN_CORPUS = [0, 3, 4, 8, 14]


@pytest.mark.parametrize("seed", KNN_CORPUS)
def test_knn_sim_seed_corpus(seed):
    from surrealdb_tpu.sim import run_knn_sim

    res = run_knn_sim(seed)
    assert res.ok, (
        f"seed {seed}: violations={res.violations[:4]} "
        f"errors={res.errors[:2]}"
    )
    assert res.stats["acked"] > 0
    assert res.stats["answered"] > 0


def test_knn_sim_bit_reproducible():
    from surrealdb_tpu.sim import run_knn_sim

    a = run_knn_sim(7)
    b = run_knn_sim(7)
    assert a.trace_digest == b.trace_digest
    assert a.store_digest == b.store_digest
    c = run_knn_sim(8)
    assert c.trace_digest != a.trace_digest


def test_knn_sim_exercises_partial_answers():
    """The corpus is not vacuous: across a handful of seeds the fault
    schedule actually produces flagged partial answers AND typed
    errors — the paths check_knn_delivery exists to police."""
    from surrealdb_tpu.sim import run_knn_sim

    partial = errors = 0
    for seed in KNN_CORPUS:
        res = run_knn_sim(seed)
        partial += res.stats["partial"]
        errors += res.stats["errors"]
    assert partial > 0
    assert errors > 0


def test_knn_sim_silent_loss_mutation_caught(monkeypatch):
    """Mutation test: a router that silently drops per-shard failures
    (short answers, no partial flag — the classic silently-wrong
    distributed KNN) must be caught by check_knn_delivery."""
    from surrealdb_tpu.idx import shardvec
    from surrealdb_tpu.sim import run_knn_sim

    def broken(self, qv, fetch, ctx, memo=None):
        pairs, _failures = shardvec.scatter_gather(self, qv, fetch, ctx)
        return pairs  # failures dropped on the floor

    monkeypatch.setattr(shardvec.ShardedVectorIndex, "_search", broken)
    caught = 0
    for seed in range(12):
        res = run_knn_sim(seed)
        if any("SILENT LOSS" in v or "STILL PARTIAL" in v
               or "ORACLE" in v for v in res.violations):
            caught += 1
    assert caught >= 1, "silently dropped shards were not detected"


@pytest.mark.parametrize("seed", [0, 4, 14])
def test_knn_sim_with_segments_enabled(monkeypatch, seed):
    """The KNN delivery invariants hold with segmented ANN serving
    forced on every part engine (PR 15): seals, background builds and
    merges race the chaos schedule, and every non-partial answer must
    still equal the brute oracle. Oversampling is pinned high enough
    that graph-served segments re-rank their whole span exactly — the
    checker demands exactness, and the point here is the segment
    MACHINERY (fan-out, merge_topk, dirty rows, splices) under faults,
    not descent recall."""
    from surrealdb_tpu import cnf
    from surrealdb_tpu.idx import segments, vector
    from surrealdb_tpu.sim import run_knn_sim

    monkeypatch.setattr(cnf, "KNN_SEG_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS", 16)
    monkeypatch.setattr(cnf, "KNN_ANN_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_ANN_OVERSAMPLE", 4096)
    monkeypatch.setattr(cnf, "KNN_HOST_BATCH", "host")
    # route even tiny part searches through knn_batch (the segment
    # fan-out entry) instead of the small-store single-pass shortcut
    monkeypatch.setattr(vector, "DEVICE_MIN_ROWS", 8)
    segments.reset_counters()
    res = run_knn_sim(seed)
    assert res.ok, (
        f"seed {seed} with segments: violations={res.violations[:4]} "
        f"errors={res.errors[:2]}"
    )
    assert res.stats["answered"] > 0
    c = segments.counters()
    assert c["seg_seals"] >= 1, "segments never engaged — vacuous run"
    assert c["ann_full_rebuilds"] == 0


@pytest.mark.slow
def test_knn_sim_sweep_60_seeds():
    """Acceptance sweep: >=60 seeds of index-serving chaos — splits,
    primary SIGKILL, asymmetric partitions racing KNN queries — with
    check_knn_delivery green on every one."""
    from surrealdb_tpu.sim import run_knn_sim

    fails = []
    for seed in range(2000, 2060):
        res = run_knn_sim(seed)
        if not res.ok:
            fails.append((seed, res.violations[:3], res.errors[:2]))
    assert not fails, f"{len(fails)} failing seeds: {fails[:5]}"
