"""Kind `knn_rw` and the cell `exact128rw.rw95-c32` (CPU, `--rehearsal`
sizes; not tier-1).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_knn_rw_kind.py -q

A rehearsal proves control flow, counts and answers, never a time.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "exact128rw.rw95-c32"
NEW_LAYERS = ("index_sync_us", "vec_append_ms", "vec_full_ships_in_window",
              "vec_append_roofline")


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


rw = load(os.path.join(BENCH, "kinds", "knn_rw.py"), "t_knn_rw")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
with open(os.path.join(BENCH, "configs", "exact128rw.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "configs", "exact128.json")) as f:
    EXACT128 = json.load(f)


def reader(name):
    return load(os.path.join(BENCH, "layers", name + ".py"), "t_" + name)


def run_cell(*args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearsal", *args],
        capture_output=True, text=True, timeout=600, env=full, cwd=ROOT)
    lines = out.stdout.strip().splitlines()
    return out, (json.loads(lines[-1]) if lines else None)


COMPARED = {"bad_answers", "phantom_rows", "dist_err_max", "recall_at_10",
            "readback_missing", "readback_queries", "answers_compared",
            "insert_failed", "inserts_acknowledged", "pool_wrapped",
            "host_served_events", "device_dispatches", "appended_rows",
            "appended_rows_over", "full_ships", "warmup_rows_off",
            "failed_requests"}


# -- the cell end to end -------------------------------------------------------


def test_the_cell_prints_the_contracts_line_and_refuses_the_control():
    out, res = run_cell("--seed", "2147484201", "--seconds", "3",
                        "--trace", "0", "--control")
    assert out.returncode == 0, out.stderr[-3000:]
    assert res["rehearsal"] is True and res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "latency_p50_ms", "latency_p95_ms",
                                   "recall_at_10", "setup_s"}
    c = res["compared"]
    assert set(c) == COMPARED and list(res)[-1] == "compared"
    assert c["inserts_acknowledged"]["value"] >= 5
    assert 1 <= c["appended_rows"]["value"] \
        <= c["inserts_acknowledged"]["value"] + CONFIG["clients"]
    assert c["readback_queries"]["value"] >= 4
    assert c["full_ships"]["value"] == 0
    # the bf16 reference in the program's place, over the same visible rows
    assert res["control"]["correct"] is False
    assert not res["control"]["dist_err_max"]["ok"]
    assert res["control"]["bad_answers"]["ok"]
    assert res["control"]["phantom_rows"]["ok"]
    assert res["control"]["readback_missing"]["ok"]


def test_the_traced_cell_prints_the_write_paths_metrics():
    out, res = run_cell("--seed", "11", "--seconds", "3", "--trace", "1")
    assert out.returncode == 0, out.stderr[-3000:]
    assert res["correct"] is True
    m = res["metrics"]
    assert m["index_sync_us"]["value"] > 0
    assert m["vec_append_ms"]["value"] > 0
    assert m["vec_full_ships_in_window"] == {"value": 0, "unit": "ships"}
    assert m["compiles_in_window"]["value"] == 0
    # the CPU backend's trace has no device plane, so no program's device
    # seconds (the roofline share is a chip run's), and no allocator stats
    listed = {p["name"] for p in BENCHMARK["per_layer"]
              if "workloads" not in p or CELL in p["workloads"]}
    assert set(m) == listed - {"vec_append_roofline", "device_bytes_in_use"}


@pytest.mark.parametrize("fault,says,numbers", [
    ("mask", "vec_append drops every fifth row's mask bit",
     ("readback_missing",)),
    ("shift", "vec_append writes new rows one place off",
     ("dist_err_max", "readback_missing")),
    ("sync", "every other index sync skips the log", ("readback_missing",)),
])
def test_a_fault_under_the_timed_path_is_not_correct(fault, says, numbers):
    """rw_faults/: a row on the chip that no search finds, a row written
    one place off, a search that rides without the rows its transaction can
    read. Set-up's own checks pass; the window carries the fault."""
    out, res = run_cell("--seed", "2147485107", "--seconds", "3",
                        "--trace", "0", BENCH_FAULT_RW=fault,
                        PYTHONPATH=os.path.join(HERE, "rw_faults"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"[fault] {says}" in out.stderr
    assert res["correct"] is False
    for name in numbers:
        assert not res["compared"][name]["ok"], name
    assert res["compared"]["insert_failed"]["ok"]
    assert res["compared"]["device_dispatches"]["ok"]
    assert res["compared"]["full_ships"]["ok"]
    if fault != "shift":
        # (a row written past the rows the host knows comes back as a
        # slot it drops: an answer of nine rows)
        assert res["compared"]["bad_answers"]["ok"]
        assert res["compared"]["dist_err_max"]["ok"]


# -- the pool, the rule and the comparison, without a server -------------------


def test_the_pool_reads_every_insert_back_by_its_own_caller():
    rng = np.random.default_rng(3)
    insert_at, on_row = rw.lay_out_pool(4096, 32, 204, rng)
    assert len(insert_at) == len(set(insert_at)) == 204
    assert insert_at == sorted(insert_at)
    taken = set(insert_at)
    for w, i in enumerate(insert_at):
        assert on_row[i + 32] == w and i + 32 not in taken
    # and once more by another caller, later in the pool
    others = [(j, w) for j, w in on_row.items()
              if j != insert_at[w] + 32]
    assert len(others) >= 200
    for j, w in others:
        assert j > insert_at[w] + 32 and (j - insert_at[w]) % 32
        assert j not in taken
    # any stretch holds about the share
    per_kilo = np.histogram(insert_at, bins=4, range=(0, 4096))[0]
    assert per_kilo.min() >= 30 and per_kilo.max() <= 75
    with pytest.raises(rw.SetupFailed):
        rw.lay_out_pool(256, 32, 200, np.random.default_rng(1))


def tiny(seed=5, n=2000, dim=32, pool=512, clients=8, share=0.05):
    """A deployment without a server: rows, pool and the kind's own
    layout, as `setup` puts them together."""
    writes = int(pool * share)
    xs, rng = rw.knn.clustered_rows(n + writes, dim, seed)
    insert_at, on_row = rw.lay_out_pool(pool, clients, writes, rng)
    ops = np.zeros(pool, np.int8)
    ops[insert_at] = rw.INSERT
    rows = rng.integers(0, n, pool)
    rows[insert_at] = n + np.arange(writes)
    for j, w in on_row.items():
        rows[j] = n + w
    pool_q = rw.knn.queries_near(xs, rows, rng)
    pool_q[insert_at] = xs[n:]
    sz = {"rows": n, "dim": dim, "k": 10, "metric": "euclidean",
          "table": "t", "clients": clients, "runner_op": "vec_knn"}
    return rw.Deployment(sz, xs, ops, rows, pool_q, {})


LIMITS = dict(CONFIG["limits"], compare_max=100000, readback_queries_min=1)


def insert_reply(row):
    return json.dumps({"id": 0, "result": [
        {"status": "OK", "result": [{"id": f"t:{row}"}]}]}).encode()


def search_reply(rows, dists):
    return json.dumps({"id": 0, "result": [{"status": "OK", "result": [
        {"id": f"t:{int(r)}", "d": float(d)}
        for r, d in zip(rows, dists)]}]}).encode()


def play(dep, start_at, stop_at, visible_lag=0.0, mutate=None):
    """The window's records of a store that serves the pool positions
    [start_at, stop_at) one after the other, 1 ms a request, every INSERT
    visible `visible_lag` seconds after it was sent (0: at once). The
    positions before `start_at` were warm-up's."""
    n0 = dep.n0
    live = np.zeros(len(dep.xs), bool)
    live[:n0] = True
    for p in range(start_at):
        if dep.ops[p] == rw.INSERT:
            live[dep.rows[p]] = True
    pending = []
    records = []
    for step, p in enumerate(range(start_at, stop_at)):
        sent = 100.0 + step * 1e-3
        received = sent + 5e-4
        for at, row in list(pending):
            if at <= sent:
                live[row] = True
                pending.remove((at, row))
        if dep.ops[p] == rw.INSERT:
            pending.append((sent + visible_lag, int(dep.rows[p])))
            if not visible_lag:
                live[dep.rows[p]] = True
                pending.pop()
            records.append((p, sent, received, 200,
                            insert_reply(int(dep.rows[p]))))
            continue
        ids = np.flatnonzero(live)
        d = np.linalg.norm(dep.xs[ids].astype(np.float64)
                           - dep.pool_q[p].astype(np.float64), axis=1)
        order = np.argsort(d, kind="stable")[:10]
        rows, dists = ids[order], d[order]
        if mutate is not None:
            rows, dists = mutate(p, rows, dists)
        records.append((p, sent, received, 200, search_reply(rows, dists)))
    return records


def snapshots(dep, records, start_at, ships=0, appended=None):
    warm = int((dep.ops[:start_at] == rw.INSERT).sum())
    acks = sum(1 for r in records if dep.ops[r[0]] == rw.INSERT)
    sup = {"state": "ready", "vec_append_rows": 10, "vec_full_ships": 1,
           **{c: 0 for c in rw.knn.COUNTERS}}
    before = {"supervisor": dict(sup),
              "runner": {"ops": {"vec_knn": 5},
                         "vec": {"vec/x": {"rows": dep.n0 + warm,
                                           "capacity": 1 << 20}}}}
    after = {"supervisor": dict(
        sup, vec_append_rows=10 + (acks if appended is None else appended),
        vec_full_ships=1 + ships),
        "runner": {"ops": {"vec_knn": 50}}}
    return before, after


def verdict(dep, records, start_at, **kw):
    says = []
    before, after = snapshots(dep, records, start_at, **kw)
    out = dep.judge(records, before, after, LIMITS, 1, says.append)
    return out, says


def test_a_sound_window_is_correct_and_knows_what_warm_up_sent():
    dep = tiny()
    # warm-up walked the first 128 positions: every caller 16 of its share
    records = play(dep, 128, 512)
    out, says = verdict(dep, records, 128)
    c = out["compared"]
    assert not says and all(v["ok"] for v in c.values()), (says, c)
    assert all(out["ok"]) and len(out["ok"]) == len(records)
    assert c["recall_at_10"]["value"] == 1.0
    assert c["dist_err_max"]["value"] < 1e-9
    assert c["readback_queries"]["value"] >= 20
    assert c["inserts_acknowledged"]["value"] \
        == int((dep.ops[128:] == rw.INSERT).sum())
    # a search that sits on a row warm-up inserted needs the walk to know it
    vis = rw.Visibility(dep, records, [dep.parse(r) for r in records])
    assert vis.warmup == int((dep.ops[:128] == rw.INSERT).sum()) > 0
    assert vis.wrapped == 0


def test_the_three_classes_of_the_visibility_rule():
    dep = tiny()
    records = play(dep, 0, 512)
    vis = rw.Visibility(dep, records, [dep.parse(r) for r in records])
    w = 3                                   # the fourth INSERT of the pool
    b, a = vis.sent[w], vis.acked[w]
    assert np.isfinite(b) and a == pytest.approx(b + 5e-4)
    must, may = vis.classes(a + 1e-6, a + 1e-3)       # sent after the ack
    assert must[w] and not may[w]
    must, may = vis.classes(b - 1e-4, b + 1e-4)       # overlaps the INSERT
    assert not must[w] and may[w]
    must, may = vis.classes(a - 1e-6, a + 1e-3)       # sent just before it
    assert not must[w] and may[w]
    must, may = vis.classes(b - 2e-3, b - 1e-3)       # answered before sent
    assert not must[w] and not may[w]
    # an INSERT the window never reached is never visible
    short = play(dep, 0, 256)
    vis = rw.Visibility(dep, short, [dep.parse(r) for r in short])
    never = int((dep.ops[:256] == rw.INSERT).sum())
    assert np.isinf(vis.sent[never:]).all() and (vis.sent[never:] > 0).all()
    must, may = vis.classes(1e9, 2e9)
    assert not must[never:].any() and not may[never:].any()
    assert must[:never].all()


def test_a_may_see_row_is_right_held_or_not():
    """An INSERT that becomes visible 10 ms after it was sent: searches
    sent meanwhile may hold the row or not; once acknowledged it must be
    there."""
    dep = tiny()
    for lag in (0.0, 2e-4):
        out, says = verdict(dep, play(dep, 64, 512, visible_lag=lag), 64)
        assert not says, says
        assert all(v["ok"] for v in out["compared"].values())


def test_a_write_that_is_late_is_missed():
    dep = tiny()
    # visible 40 ms after it was sent: the caller's next request, 8 ms on,
    # does not find the row it was acknowledged
    out, says = verdict(dep, play(dep, 64, 512, visible_lag=0.04), 64)
    c = out["compared"]
    assert not c["readback_missing"]["ok"]
    assert c["readback_missing"]["value"] >= 5
    assert any("did not come back" in s for s in says)
    assert c["phantom_rows"]["ok"] and c["dist_err_max"]["ok"]


def test_a_row_seen_before_it_was_sent_is_a_phantom():
    dep = tiny()
    later = int(dep.rows[np.flatnonzero(dep.ops == rw.INSERT)[-1]])

    pos = next(p for p in range(100, 200) if dep.ops[p] == rw.SEARCH)

    def early(p, rows, dists):
        if p == pos:
            rows = rows.copy()
            rows[-1] = later
        return rows, dists

    out, says = verdict(dep, play(dep, 64, 400, mutate=early), 64)
    c = out["compared"]
    assert c["phantom_rows"]["value"] == 1 and not c["phantom_rows"]["ok"]
    assert any("nobody had sent" in s for s in says)


def test_a_distance_of_another_row_and_a_refused_insert_are_caught():
    dep = tiny()

    def neighbour(p, rows, dists):
        if p % 7 == 0:
            dists = dists.copy()
            dists[0] = dists[0] + 0.01
            dists.sort()
        return rows, dists

    out, _says = verdict(dep, play(dep, 64, 512, mutate=neighbour), 64)
    assert not out["compared"]["dist_err_max"]["ok"]
    records = play(dep, 64, 512)
    j = next(j for j, r in enumerate(records) if dep.ops[r[0]] == rw.INSERT)
    p = records[j][0]
    records[j] = records[j][:4] + (json.dumps({"id": 0, "result": [{
        "status": "ERR", "result": "Database record `t:1` already exists"
    }]}).encode(),)
    out, says = verdict(dep, records, 64)
    c = out["compared"]
    assert c["insert_failed"]["value"] == 1 and not out["ok"][j]
    assert any(f"pool position {p}" in s for s in says)
    # a refused row is nobody's must-see: the search on it is no read-back
    assert c["readback_missing"]["ok"]


def test_a_pool_made_too_small_trips_pool_wrapped():
    dep = tiny()
    records = play(dep, 0, 512)
    again = [(r[0], r[1] + 1.0, r[2] + 1.0, r[3], r[4])
             for r in records[:40]]
    out, says = verdict(dep, records + again, 0)
    c = out["compared"]
    assert c["pool_wrapped"]["value"] == 40 and not c["pool_wrapped"]["ok"]
    assert any("too small" in s for s in says)


def test_the_store_has_to_grow_in_place():
    dep = tiny()
    records = play(dep, 64, 512)
    out, says = verdict(dep, records, 64, ships=1)
    assert not out["compared"]["full_ships"]["ok"]
    assert any("shipped again" in s for s in says)
    out, _ = verdict(dep, records, 64, appended=0)
    assert not out["compared"]["appended_rows"]["ok"]
    out, _ = verdict(dep, records, 64, appended=10_000)
    assert not out["compared"]["appended_rows_over"]["ok"]
    # the chip held other rows at the window's start than the walk says
    before, after = snapshots(dep, records, 64)
    before["runner"]["vec"]["vec/x"]["rows"] += 3
    out = dep.judge(records, before, after, LIMITS, 1, lambda s: None)
    assert out["compared"]["warmup_rows_off"]["value"] == 3
    before["runner"]["vec"]["vec/x"]["rows"] -= 3 + dep.clients + 2
    out = dep.judge(records, before, after, LIMITS, 1, lambda s: None)
    assert out["compared"]["warmup_rows_off"]["value"] == 2


def test_the_control_fails_on_its_distances():
    dep = tiny()
    records = play(dep, 64, 512)
    out = dep.judge_control(records, LIMITS, 1)
    assert out["correct"] is False and not out["dist_err_max"]["ok"]
    assert out["phantom_rows"]["ok"] and out["bad_answers"]["ok"]
    assert out["dist_err_max"]["value"] > 1e-4


# -- the new readers -----------------------------------------------------------


WINDOW = {
    "requests": 20_000, "answers": 20_000, "seconds": 30.0,
    "config": {"rows": 100_000, "dim": 128, "k": 10},
    "stages": {"index_sync": {"count": 1500, "total_us": 600_000.0},
               "vec_append": {"count": 800, "total_us": 960_000.0}},
    "batching": {"dispatches": 2400, "riders": 19_000},
    "before": {"supervisor": {"vec_full_ships": 1, "vec_appends": 300,
                              "vec_append_rows": 1500},
               "runner": {"cc": {"misses": 20}}},
    "after": {"supervisor": {"vec_full_ships": 1, "vec_appends": 1100,
                             "vec_append_rows": 2500},
              "runner": {"cc": {"misses": 20}}},
    "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "trace": {"busy_s": 0.02, "window_s": 3.0, "programs": {
        "jit_vec_append": {"runs": 80, "seconds": 0.0016}}},
}
# a delta of 1.25 rows: each row read, written in f32 and bf16, 9 B beside
RUN_BYTES = 1.25 * (128 * 4 + 128 * 6 + 9)


@pytest.mark.parametrize("name,want", [
    ("index_sync_us", 30.0),
    ("vec_append_ms", 1.2),
    ("vec_full_ships_in_window", 0),
    ("vec_append_roofline", 100 * 80 * (RUN_BYTES / 819e9) / 0.0016),
])
def test_layer_reader(name, want):
    assert reader(name).read(WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW_LAYERS)
def test_layer_reader_with_nothing_to_read_returns_nothing(name):
    """A program from before this cell has no such stage, counter or
    program: the reader returns nothing and does not raise."""
    empty = dict(WINDOW, stages={}, trace={"busy_s": 0.1, "window_s": 3.0,
                                          "programs": {}},
                 before={"supervisor": {"host_routed": 0},
                         "runner": {"cc": {"misses": 7}}},
                 after={"supervisor": {"host_routed": 0},
                        "runner": {"cc": {"misses": 7}}})
    assert reader(name).read(empty) is None
    assert reader(name).read(dict(empty, trace=None, peaks=None)) is None


def test_the_roofline_counts_what_the_write_needs_once():
    costs = reader("vec_append_roofline").costs
    assert costs(1.25, 128) == (1.25 * 128, RUN_BYTES)
    least = RUN_BYTES / 819e9
    slow = dict(WINDOW, trace={"programs": {
        "jit_vec_append": {"runs": 10, "seconds": 10 * least}}})
    assert reader("vec_append_roofline").read(slow) == pytest.approx(100.0)
    assert reader("vec_append_roofline").read(WINDOW) < 1.0


def test_the_benchmark_gained_the_cell_and_its_readers_and_nothing_else():
    by = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW_LAYERS:
        assert by[name]["workloads"] == [CELL]
    assert [m["name"] for m in BENCHMARK["per_layer"]][-4:] \
        == list(NEW_LAYERS)
    cell = BENCHMARK["workloads"][-1]
    assert cell == dict(cell, name=CELL, config="exact128rw",
                        traffic="rw95-c32", chips=1)
    assert len(cell["why"]) <= 200
    entry = BENCHMARK["configs"][-1]
    assert entry["name"] == "exact128rw" and entry["reduced"] == [] \
        and entry["file"] == "benchmark/configs/exact128rw.json"
    assert len(entry["source"]) <= 200 and "YCSB" in entry["source"] \
        and "config 1" in entry["source"]
    assert entry["source"] == CONFIG["source"]
    assert BENCHMARK["run_seconds"] == 30
    # config 1's shapes, and exact128's limits where the arithmetic is its
    for key in ("rows", "dim", "metric", "k", "sql_rows", "index"):
        assert CONFIG[key] == EXACT128[key], key
    for key in ("dist_floor", "dist_err_max", "recall_at_10_min",
                "compare_max"):
        assert CONFIG["limits"][key] == EXACT128["limits"][key], key
    assert CONFIG["write_share"] == 0.05 and CONFIG["reduced"] == []
    assert set(CONFIG["assumed"]) == {"inserts", "data", "pool", "clients"}
    with open(os.path.join(BENCH, "traffic", "rw95-c32.json")) as f:
        traffic = json.load(f)
    assert traffic["processes"] * traffic["threads_per_process"] \
        == CONFIG["clients"]
