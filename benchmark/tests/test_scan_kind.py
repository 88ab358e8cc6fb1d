"""Kind `scan` and the cell `scan768.scan-c32` (CPU, `--rehearsal` sizes;
not tier-1).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_scan_kind.py -q

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
CELL = "scan768.scan-c32"
NEW_LAYERS = ("scan_topk_us", "scan_fetch_us", "col_ships_in_window",
              "scan_topk_roofline")


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


scan = load(os.path.join(BENCH, "kinds", "scan.py"), "t_scan")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
with open(os.path.join(BENCH, "configs", "scan768.json")) as f:
    CONFIG = json.load(f)


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


# -- the cell end to end -------------------------------------------------------


def test_the_cell_prints_the_contracts_line_and_refuses_the_control():
    out, res = run_cell("--seed", "2147484101", "--seconds", "2",
                        "--trace", "0", "--control")
    assert out.returncode == 0, out.stderr[-3000:]
    assert res["rehearsal"] is True and res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "latency_p50_ms", "latency_p95_ms",
                                   "recall_at_10", "setup_s"}
    assert res["metrics"]["recall_at_10"]["value"] \
        >= CONFIG["limits"]["recall_at_10_min"]
    c = res["compared"]
    assert {"bad_answers", "score_err_max", "order_rise_max", "recall_at_10",
            "readback_missing", "readback_queries", "host_served_events",
            "device_dispatches", "scan_riders_off", "failed_requests"} \
        <= set(c)
    assert c["device_dispatches"]["value"] >= 1
    assert c["readback_queries"]["value"] >= 1
    assert c["scan_riders_off"]["value"] == 0
    assert list(res)[-1] == "compared"
    # the bf16 reference in the program's place
    assert res["control"]["correct"] is False
    assert not res["control"]["recall_at_10"]["ok"]
    assert not res["control"]["score_err_max"]["ok"]
    assert res["control"]["bad_answers"]["ok"]


def test_the_traced_cell_prints_the_scans_metrics():
    out, res = run_cell("--seed", "7", "--seconds", "3", "--trace", "1")
    assert out.returncode == 0, out.stderr[-3000:]
    assert res["correct"] is True
    m = res["metrics"]
    assert m["scan_topk_us"]["value"] > 0 and m["scan_topk_us"]["unit"] == "us"
    assert m["scan_fetch_us"]["value"] > 0
    assert m["col_ships_in_window"] == {"value": 0, "unit": "ships"}
    assert m["riders_per_dispatch"]["value"] > 1
    # the scan is the batcher's client: its stage holds the wait and ride
    assert m["scan_topk_us"]["value"] >= m["batch_ride_us"]["value"]
    # the CPU backend's trace has no device plane, so no program's
    # device seconds: the roofline share is a chip run's
    assert "scan_topk_roofline" not in m
    listed = {p["name"] for p in BENCHMARK["per_layer"]
              if "workloads" not in p or CELL in p["workloads"]}
    assert set(m) <= listed and set(NEW_LAYERS) <= listed


@pytest.mark.parametrize("fault,says,numbers", [
    ("host", "every fifth scan scored on the host", ("scan_riders_off",)),
    ("bf16", "exact_scan ranks in bfloat16", ("recall_at_10",)),
])
def test_a_fault_under_the_timed_path_is_not_correct(fault, says, numbers):
    """scan_faults/: the host answering in the device's place without
    saying so, and a bf16 rank in the exact program's place. Set-up's
    lone scans pass its checks; the window carries the fault."""
    out, res = run_cell("--seed", "2147485007", "--seconds", "2",
                        "--trace", "0", BENCH_FAULT_SCAN=fault,
                        PYTHONPATH=os.path.join(HERE, "scan_faults"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"[fault] {says}" in out.stderr
    assert res["correct"] is False
    for name in numbers:
        assert not res["compared"][name]["ok"], name
    assert res["compared"]["bad_answers"]["ok"]
    assert res["compared"]["score_err_max"]["ok"]
    assert res["compared"]["device_dispatches"]["ok"]


# -- the comparison and its control, without a server --------------------------


LIMITS = dict(CONFIG["limits"], compare_max=1000)


def tiny(seed=5, n=3000, dim=48, n_sql=32, pool=96):
    xs, rng = scan.clustered_rows(n, dim, seed)
    near = np.concatenate([rng.integers(n - n_sql, n, pool // 2),
                           rng.integers(0, n - n_sql, pool - pool // 2)])
    qs = scan.queries_near(xs, near, rng)
    sz = {"rows": n, "dim": dim, "k": 10, "sql_rows": n_sql,
          "runner_op": "vec_knn", "statement": CONFIG["statement"]}
    on_sql = np.arange(pool) < pool // 2
    return scan.Deployment(sz, xs, qs, near, on_sql, {})


def reference_answers(dep):
    ids, sims = scan.top_similar(dep.xs, dep.pool_q, 10)
    return [(i, (ids[i].tolist(), sims[i].tolist()))
            for i in range(len(dep.pool_q))]


def test_the_reference_is_plain_f64_cosine():
    dep = tiny()
    ids, sims = scan.top_similar(dep.xs, dep.pool_q[:4], 10, threads=1)
    x, q = dep.xs.astype(np.float64), dep.pool_q[:4].astype(np.float64)
    full = (q @ x.T) / (np.linalg.norm(q, axis=1)[:, None]
                        * np.linalg.norm(x, axis=1)[None, :])
    want = np.argsort(-full, axis=1, kind="stable")[:, :10]
    assert np.array_equal(ids, want)
    assert np.allclose(sims, np.take_along_axis(full, want, 1), atol=1e-15)
    assert np.allclose(scan.row_similarities(dep.xs, dep.pool_q[:4], want),
                       sims, atol=1e-15)


def test_compare_accepts_the_reference_and_refuses_the_control():
    dep = tiny()
    says = []
    got = scan.compare(dep, reference_answers(dep), LIMITS, says.append)
    assert all(c["ok"] for c in got.values()) and not says
    assert got["recall_at_10"]["value"] == 1.0
    assert got["score_err_max"]["value"] < 1e-14
    assert got["readback_queries"]["value"] == 48
    idx = list(range(len(dep.pool_q)))
    control = list(zip(idx, scan.control_answers(dep.xs, dep.pool_q, 10)))
    got = scan.compare(dep, control, LIMITS, says.append)
    assert not got["score_err_max"]["ok"] and says
    assert got["score_err_max"]["value"] > 1e-5
    assert not got["recall_at_10"]["ok"]
    assert got["bad_answers"]["ok"]


@pytest.mark.parametrize("alter,number", [
    (lambda ids, s: ([(ids[0] + 1501) % 3000] + ids[1:], s), "recall_at_10"),
    (lambda ids, s: (ids, [s[0] + 1e-6] + s[1:]), "score_err_max"),
    (lambda ids, s: (ids[1::-1] + ids[2:], s[1::-1] + s[2:]),
     "order_rise_max"),
])
def test_one_altered_answer_is_counted(alter, number):
    dep = tiny()
    answers = reference_answers(dep)
    # an answer off the SQL rows whose first two similarities differ well
    i = next(i for i, (ids, s) in answers
             if not dep.on_sql[i] and s[0] - s[1] > 1e-4)
    answers[i] = (i, alter(*answers[i][1]))
    says = []
    limits = dict(LIMITS, recall_at_10_min=1.0)
    got = scan.compare(dep, answers, limits, says.append)
    assert not got[number]["ok"] and f"query {i}" in says[0]
    others = {"recall_at_10", "score_err_max", "order_rise_max"} - {number}
    if number != "recall_at_10":    # a foreign row's s is off too
        assert all(got[o]["ok"] for o in others)


def test_a_lost_sql_row_is_not_read_back():
    dep = tiny()
    answers = reference_answers(dep)
    i = next(i for i in range(len(dep.pool_q)) if dep.on_sql[i])
    ids, sims = answers[i][1]
    assert ids[0] == dep.pool_rows[i]
    far = int(np.argmin(scan.row_similarities(
        dep.xs, dep.pool_q[i:i + 1], np.arange(3000)[None, :])))
    answers[i] = (i, (ids[1:] + [far], sims[1:] + [sims[-1]]))
    says = []
    got = scan.compare(dep, answers, LIMITS, says.append)
    assert got["readback_missing"]["value"] == 1
    assert any("did not come back" in t for t in says)


def test_compare_counts_what_is_no_answer():
    dep = tiny()
    answers = reference_answers(dep)

    def reply(rows):
        return json.dumps({"id": 1, "result": [
            {"status": "OK", "result": rows}]}).encode()

    ten = [{"id": f"vec768:{j}", "s": 0.9 - j / 100} for j in range(10)]
    assert scan.parse_answer(200, reply(ten), 10) \
        == (list(range(10)), [0.9 - j / 100 for j in range(10)])
    answers[3] = (3, scan.parse_answer(503, b"busy", 10))
    answers[4] = (4, scan.parse_answer(200, reply(ten[:9]), 10))
    answers[5] = (5, scan.parse_answer(200, json.dumps(
        {"id": 5, "error": {"code": -32000, "message": "no"}}).encode(), 10))
    answers[6] = (6, (list(range(9)) + [3000], [0.5] * 10))
    answers[7] = (7, scan.parse_answer(
        200, reply(ten[:9] + [{"id": "vec768:9", "s": None}]), 10))
    answers[8] = (8, scan.parse_answer(200, reply(ten[:9] + ten[:1]), 10))
    says = []
    got = scan.compare(dep, answers, LIMITS, says.append)
    assert got["bad_answers"]["value"] == 6 and not got["bad_answers"]["ok"]
    assert "status 503" in says[0]
    assert "9 rows" in answers[4][1] and "rpc error" in answers[5][1]
    assert "unreadable" in answers[7][1] and "9 distinct" in answers[8][1]


def test_requests_bind_the_vector_and_carry_the_sources_statement():
    sz = {"statement": CONFIG["statement"]}
    q = np.asarray([0.25, -1.5, 3.0], np.float32)
    req = json.loads(scan.rpc_body(sz, 7, q))
    text, variables = req["params"]
    assert scan.PATH == "/rpc" and req["method"] == "query" \
        and req["id"] == 7
    assert text == ("SELECT id, vector::similarity::cosine(emb, $q) AS s "
                    "FROM vec768 ORDER BY s DESC LIMIT 10")
    assert variables == {"q": [0.25, -1.5, 3.0]}
    assert "INDEX" not in json.dumps(CONFIG["statement"]).upper()


def test_device_served_needs_a_rider_a_request():
    sup = {c: 0 for c in scan.COUNTERS}

    def snap(ops, riders, **moved):
        return {"supervisor": dict(sup, state="ready", **moved),
                "runner": {"ops": {"vec_knn": ops},
                           "scan": {"riders": riders}}}

    before, says = snap(5, 40), []
    got = scan.device_served(before, snap(9, 72), "vec_knn", 32,
                             says.append)
    assert all(c["ok"] for c in got.values()) and not says
    got = scan.device_served(before, snap(9, 70), "vec_knn", 32,
                             says.append)
    assert got["scan_riders_off"]["value"] == 2 and "30 riders" in says[0]
    got = scan.device_served(before, snap(9, 72, host_routed=2), "vec_knn",
                             32, says.append)
    assert not got["host_served_events"]["ok"]
    # a runner from before the counters: no rider was counted
    old = {"supervisor": dict(sup, state="ready"),
           "runner": {"ops": {"vec_knn": 9}}}
    got = scan.device_served(before, old, "vec_knn", 32, says.append)
    assert not got["scan_riders_off"]["ok"]


def test_the_runner_probe_refuses_a_program_without_the_block():
    class Sup:
        def __init__(self, status):
            self.status = status

        def runner_status(self):
            return self.status

    assert scan.runner_scans(Sup({"scan": {"riders": 3}})) == {"riders": 3}
    for status in ({}, {"scan": None}, {"csr": {"bag_riders": 1}}):
        with pytest.raises(scan.SetupFailed, match="no exact column block"):
            scan.runner_scans(Sup(status))


def test_the_bulk_route_writes_the_programs_own_records():
    sys.path.insert(0, ROOT)
    try:
        from surrealdb_tpu import Datastore
        from surrealdb_tpu import key as K
        from surrealdb_tpu.kvs.api import deserialize, serialize
        from surrealdb_tpu.val import RecordId
    finally:
        sys.path.remove(ROOT)
    xs, _rng = scan.clustered_rows(300, 24, 3)
    ds = Datastore("memory")
    try:
        ds.query("DEFINE TABLE t", ns=scan.NS, db=scan.DB)
        scan.bulk_documents(ds, "t", xs, chunk=128)
        txn = ds.transaction(write=False)
        try:
            for i in (0, 127, 128, 299):
                raw = txn.get(K.record(scan.NS, scan.DB, "t", i))
                want = {"id": RecordId("t", i),
                        "emb": xs[i].astype(np.float64).tolist()}
                assert raw == serialize(want) and deserialize(raw) == want
        finally:
            txn.cancel()
        rows = ds.query_one("SELECT count() FROM t GROUP ALL", ns=scan.NS,
                            db=scan.DB)
        assert rows[0]["count"] == 300
    finally:
        ds.close()


# -- the new readers -----------------------------------------------------------


WINDOW = {
    "requests": 1000, "answers": 1000, "seconds": 10.0,
    "config": {"rows": 1_000_000, "dim": 768, "k": 10},
    "stages": {"vec_scan": {"count": 1000, "total_us": 60_000_000.0},
               "scan_fetch": {"count": 1000, "total_us": 4_000_000.0}},
    "batching": {"dispatches": 125, "riders": 1000},
    "before": {"supervisor": {"col_ships": 2},
               "runner": {"scan": {"riders": 500, "dispatches": 300,
                                   "rows_scored": 500_000_000}}},
    "after": {"supervisor": {"col_ships": 2},
              "runner": {"scan": {"riders": 1500, "dispatches": 425,
                                  "rows_scored": 1_500_000_000}}},
    "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "trace": {"busy_s": 0.9, "window_s": 3.0, "programs": {
        "jit_exact_scan": {"runs": 36, "seconds": 0.72}}},
}
# a dispatch of 8 riders: the 3.07 GB of f32 rows once, the batch, the reply
RUN_BYTES = 1_000_000 * 768 * 4 + 8 * 768 * 4 + 8 * 10 * 8
RUN_OPS = 2 * 8 * 1_000_000 * 768


@pytest.mark.parametrize("name,want", [
    ("scan_topk_us", 60_000.0),
    ("scan_fetch_us", 4_000.0),
    ("col_ships_in_window", 0),
    ("scan_topk_roofline", 100 * 36 * (RUN_BYTES / 819e9) / 0.72),
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
    assert reader(name).read(dict(empty, trace=None, peaks=None,
                                  batching={"dispatches": 0,
                                            "riders": 0})) is None


def test_the_roofline_counts_what_the_answer_needs_once():
    costs = reader("scan_topk_roofline").costs
    assert costs(1_000_000, 768, 10, 8) == (RUN_OPS, RUN_BYTES)
    # the bytes set the least time until a dispatch holds 481 riders
    # (2 operations a rider for every 4 bytes against the chip's 240.5)
    ops, moved = costs(1_000_000, 768, 10, 480)
    assert ops / 197e12 < moved / 819e9
    ops, moved = costs(1_000_000, 768, 10, 482)
    assert ops / 197e12 > moved / 819e9
    # and the share stays under 100 % while a run takes its least time
    least = RUN_BYTES / 819e9
    slow = dict(WINDOW, trace={"programs": {
        "jit_exact_scan": {"runs": 10, "seconds": 10 * least}}})
    assert reader("scan_topk_roofline").read(slow) == pytest.approx(100.0)


def test_the_benchmark_gained_the_cell_and_its_readers_and_nothing_else():
    by = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW_LAYERS:
        assert by[name]["workloads"] == [CELL]
    assert [m["name"] for m in BENCHMARK["per_layer"]][-4:] \
        == list(NEW_LAYERS)
    cell = BENCHMARK["workloads"][-1]
    assert cell == dict(cell, name=CELL, config="scan768",
                        traffic="scan-c32", chips=1)
    entry = BENCHMARK["configs"][-1]
    assert entry["name"] == "scan768" \
        and entry["file"] == "benchmark/configs/scan768.json"
    assert set(entry["reduced"]) <= {"rows"}
    assert ("rows" in entry["reduced"]) \
        == (CONFIG["rows"] != CONFIG["published"]["rows"])
    assert "DEFINE INDEX" not in json.dumps(CONFIG)
    assert BENCHMARK["run_seconds"] == 30
