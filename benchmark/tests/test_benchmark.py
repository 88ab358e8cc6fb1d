"""The benchmark's own tests (CPU, `--rehearsal` sizes; not tier-1).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

A rehearsal proves control flow, counts and answers, never a time.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


knn = load(os.path.join(BENCH, "kinds", "knn.py"), "t_knn")
e2e = load(os.path.join(BENCH, "metrics.py"), "t_metrics")
reduce_ = load(os.path.join(BENCH, "trace_reduce.py"), "t_reduce")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def run_cell(root, *args, env=None):
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    full.pop("BENCH_RUN", None)
    if env is not None:
        full = env
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        capture_output=True, text=True, timeout=600, env=full, cwd=root)
    lines = out.stdout.strip().splitlines()
    return out, (json.loads(lines[-1]) if lines else None)


def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


# -- every cell end to end ---------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_prints_the_contracts_line(cell):
    out, res = run_cell(ROOT, "--workload", cell, "--seed", "2147484001",
                        "--seconds", "2", "--trace", "0", "--rehearsal")
    assert out.returncode == 0, out.stderr[-3000:]
    assert RESULT_KEYS <= set(res) and list(res)[-1] == "compared"
    assert res["rehearsal"] is True and res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in BENCHMARK["end_to_end"] if applies(m, cell)}
    assert set(res["metrics"]) == want
    assert all(set(v) == {"value", "unit"} for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # the numbers compared are the last lines of stderr too
    tail = out.stderr.strip().splitlines()[-len(res["compared"]):]
    assert all(ln.startswith("compared ") for ln in tail)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traced_prints_per_layer_metrics(cell):
    out, res = run_cell(ROOT, "--workload", cell, "--seed", "7",
                        "--seconds", "3", "--trace", "1", "--rehearsal")
    assert out.returncode == 0, out.stderr[-3000:]
    assert res["correct"] is True
    names = {m["name"] for m in BENCHMARK["per_layer"] if applies(m, cell)}
    assert res["metrics"] and set(res["metrics"]) <= names
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace"))


def test_no_chip_no_result():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    for e in (env, dict(env, JAX_PLATFORMS="cpu")):
        out, res = run_cell(ROOT, "--workload", CELLS[0], "--seed", "1",
                            "--seconds", "1", "--trace", "0", env=e)
        assert out.returncode != 0 and res is None


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out, res = run_cell(str(tmp_path), "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0", "--rehearsal")
    assert out.returncode != 0 and res is None


def test_adding_a_cell_is_data_only(tmp_path):
    """A new deployment, a new traffic mix and their cell: three JSON
    files' worth of data, no edit to any file that is there."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "surrealdb_tpu"), tmp_path / "surrealdb_tpu")
    with open(os.path.join(BENCH, "configs", "exact128.json")) as f:
        cfg = json.load(f)
    cfg.update(table="vec64", index="MTREE", dim=64, metric="cosine",
               sql_rows=0, explain_in_setup=True)
    cfg["rehearsal"] = {"rows": 1500, "pool": 512}
    with open(tmp_path / "benchmark" / "configs" / "cos64.json", "w") as f:
        json.dump(cfg, f)
    with open(tmp_path / "benchmark" / "traffic" / "knn-c3.json", "w") as f:
        json.dump({"loop": "closed", "processes": 1,
                   "threads_per_process": 3}, f)
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({
        "name": "cos64", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/cos64.json"})
    bench["workloads"].append({
        "name": "cos64.knn-c3", "config": "cos64", "traffic": "knn-c3",
        "chips": 1, "why": "test"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    out, res = run_cell(str(tmp_path), "--workload", "cos64.knn-c3",
                        "--seed", "3", "--seconds", "2", "--trace", "0",
                        "--rehearsal")
    assert out.returncode == 0, out.stderr[-3000:]
    assert res["correct"] is True and res["attempted"] > 0
    assert "readback_missing" not in res["compared"]


# -- the timed path broken underneath ----------------------------------------


def _alter_distance(pairs):
    return [(rid, d * 1.001) for rid, d in pairs]


def _alter_id(pairs):
    from surrealdb_tpu.val import RecordId

    rid, d = pairs[-1]
    return pairs[:-1] + [(RecordId(rid.tb, (int(rid.id) + 7) % 900), d)]


@pytest.mark.parametrize("fault", [_alter_distance, _alter_id])
def test_an_altered_answer_is_not_correct(fault, monkeypatch, capsys):
    """Skips nothing but the look for a chip (`--rehearsal`): a whole run
    in this process, with every answer altered where the index produces
    it. `correct` has to come out false."""
    sys.path.insert(0, ROOT)
    import surrealdb_tpu.idx.vector as vec

    real = vec.TpuVectorIndex.knn

    def broken(self, *a, **kw):
        return fault(real(self, *a, **kw))

    monkeypatch.setattr(vec.TpuVectorIndex, "knn", broken)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    run = load(os.path.join(BENCH, "run.py"), "t_run")
    rc = run.main(["--workload", CELLS[0], "--seed", "11", "--seconds", "2",
                   "--trace", "0", "--rehearsal"])
    cap = capsys.readouterr()
    res = json.loads(cap.out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is False
    assert "not correct:" in cap.err


# -- the comparison and its control, without a server ------------------------


def tiny_deployment(metric="euclidean", n=3000, dim=128, nq=64, seed=5):
    xs, rng = knn.clustered_rows(n, dim, seed)
    near = rng.integers(0, n, nq)
    qs = knn.queries_near(xs, near, rng)
    sz = {"k": 10, "metric": metric, "sql_rows": 8, "runner_op": "vec_knn"}
    return knn.Deployment(sz, xs, qs, near, np.arange(nq) % 2 == 0, {})


LIMITS = {"dist_floor": 0.1, "dist_err_max": 1e-5,
          "recall_at_10_min": 0.985, "compare_max": 1000}


def exact_answers(dep):
    ref_i, ref_d = knn.brute_force(dep.xs, dep.pool_q, dep.sz["metric"], 10)
    return [(i, (ref_i[i].tolist(), ref_d[i].tolist()))
            for i in range(len(ref_i))]


@pytest.mark.parametrize("metric", ["euclidean", "cosine"])
def test_compare_accepts_the_reference_and_refuses_the_control(metric):
    dep = tiny_deployment(metric)
    says = []
    got = knn.compare(dep, exact_answers(dep), LIMITS, says.append)
    assert all(c["ok"] for c in got.values()) and not says
    assert got["recall_at_10"]["value"] == 1.0
    idx = list(range(len(dep.pool_q)))
    control = knn.control_answers(dep.xs, dep.pool_q, metric, 10)
    got = knn.compare(dep, list(zip(idx, control)), LIMITS, says.append)
    assert not got["dist_err_max"]["ok"] and says
    # bfloat16 misses the limit by orders of magnitude, not by a hair
    assert got["dist_err_max"]["value"] > 30 * LIMITS["dist_err_max"]


def test_a_corrupted_reference_is_not_correct():
    dep = tiny_deployment()
    answers = exact_answers(dep)
    dep.xs = np.roll(dep.xs, 1, axis=0)   # the reference's rows, shifted
    got = knn.compare(dep, answers, LIMITS, lambda _t: None)
    assert not got["dist_err_max"]["ok"] and not got["recall_at_10"]["ok"]


def test_compare_counts_what_is_no_answer():
    dep = tiny_deployment()
    answers = exact_answers(dep)
    answers[3] = (3, knn.parse_answer(503, b"busy", 10))
    rows, dists = answers[4][1]
    answers[4] = (4, knn.parse_answer(200, json.dumps({"id": 4, "result": [{
        "status": "OK", "result": [{"id": f"t:{r}", "d": d}
                                   for r, d in zip(rows[:9], dists)]}]}).encode(), 10))
    answers[5] = (5, knn.parse_answer(200, json.dumps(
        {"id": 5, "error": {"code": -32000, "message": "no"}}).encode(), 10))
    says = []
    got = knn.compare(dep, answers, LIMITS, says.append)
    assert got["bad_answers"]["value"] == 3 and not got["bad_answers"]["ok"]
    assert "status 503" in says[0]
    assert "rpc error" in answers[5][1] and "9 rows" in answers[4][1]


def test_requests_bind_the_vector_as_the_source_does():
    """`WHERE emb <|10|> $q` over POST /rpc: the statement's text holds no
    number of the vector, which travels as the variable `q`."""
    sz = {"k": 10, "ef": 40, "table": "t"}
    q = np.arange(4, dtype=np.float32) / 3
    req = json.loads(knn.rpc_body(sz, 7, q))
    text, variables = req["params"]
    assert knn.PATH == "/rpc" and req["method"] == "query" and req["id"] == 7
    assert text.endswith("WHERE emb <|10,40|> $q") and "[" not in text
    assert np.array_equal(np.float32(variables["q"]), q)
    assert knn.knn_sql(dict(sz, ef=None)).endswith("<|10|> $q")


def test_explain_has_to_name_the_index_and_the_operator():
    sz = {"k": 10, "ef": 40}
    plan = [{"operation": "Iterate Index", "detail": {
        "table": "t", "plan": {"index": "ix", "operator": "<|10,40|>"}}}]
    assert knn.explained(sz, plan)
    assert knn.explained(dict(sz, ef=None), plan)     # the planner's own ef
    assert not knn.explained(dict(sz, k=1, ef=None), plan)
    assert not knn.explained(dict(sz, ef=64), plan)
    assert not knn.explained(sz, [{"operation": "Iterate Table",
                                   "detail": {"table": "t"}}])


def test_a_fault_in_the_candidate_count_reaches_the_runner():
    """`kc` cut to k under the timed path (tests/faults): bf16 ranking with
    no oversampling loses ids at rehearsal size too, and nothing else."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FAULT_KC="10",
               PYTHONPATH=os.path.join(HERE, "faults"))
    out, res = run_cell(ROOT, "--workload", CELLS[0], "--seed", "2147485003",
                        "--seconds", "2", "--trace", "0", "--rehearsal",
                        env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "[fault] knn_rank_rescore runs with kc <= 10" in out.stderr
    c = res["compared"]
    assert c["recall_at_10"]["value"] < 0.97 and c["dist_err_max"]["ok"]
    assert res["correct"] is False


def test_a_lost_inserted_row_is_not_correct():
    dep = tiny_deployment()
    answers = exact_answers(dep)
    i = 0                                  # query 0 sits on an inserted row
    rows, dists = answers[i][1]
    assert rows[0] == dep.pool_rows[i]
    ref_i, ref_d = knn.brute_force(dep.xs, dep.pool_q[i:i + 1], "euclidean", 11)
    answers[i] = (i, (ref_i[0, 1:].tolist(), ref_d[0, 1:].tolist()))
    says = []
    got = knn.compare(dep, answers, LIMITS, says.append)
    assert got["readback_missing"]["value"] == 1
    assert "did not come back" in says[0]


def test_device_served_needs_quiet_counters_and_a_moving_op():
    sup = {c: 0 for c in knn.COUNTERS}
    before = {"supervisor": dict(sup, state="ready"),
              "runner": {"ops": {"vec_knn": 5}}}
    after = {"supervisor": dict(sup, state="ready"),
             "runner": {"ops": {"vec_knn": 9}}}
    says = []
    got = knn.device_served(before, after, "vec_knn", says.append)
    assert all(c["ok"] for c in got.values()) and not says
    after["supervisor"]["fallbacks"] = 1
    after["runner"]["ops"]["vec_knn"] = 5
    got = knn.device_served(before, after, "vec_knn", says.append)
    assert not got["host_served_events"]["ok"]
    assert not got["device_dispatches"]["ok"] and len(says) == 2


def test_pick_is_seeded_and_keeps_the_slowest():
    lat = [1.0] * 500
    lat[417] = 9.0
    a, b = knn.pick(lat, 50, 12345678901), knn.pick(lat, 50, 12345678901)
    assert a == b and len(a) == 50 and 417 in a
    assert knn.pick(lat, 50, 2) != a
    assert knn.pick(lat, 1000, 2) == list(range(500))


# -- end-to-end arithmetic ---------------------------------------------------


def closed_loop(clients, seconds, stall_at=None, stall_s=0.0):
    """Each client sends 10 ms requests back to back; a server stall at
    `stall_at` holds every request in flight for `stall_s` more."""
    recs = []
    for c in range(clients):
        t = 0.001 * c
        while t < seconds:
            took = 0.01
            if stall_at is not None and t <= stall_at < t + took:
                took += stall_s
            recs.append((len(recs), t, t + took, 200, True))
            t += took
    return recs


def test_a_stall_moves_p95_and_qps():
    calm = e2e.end_to_end(closed_loop(10, 1.0), 0.0, 1.0)
    hurt = e2e.end_to_end(closed_loop(10, 1.0, 0.3, 0.5), 0.0, 1.0)
    assert calm["qps"] == pytest.approx(1000.0, rel=0.02)
    assert calm["latency_p95_ms"] == pytest.approx(10.0)
    # half a second in which nobody is answered: half the answers, and
    # the ten held requests are 2% of 510 - under the 95th percentile
    assert hurt["qps"] == pytest.approx(500.0, rel=0.03)
    assert hurt["latency_p50_ms"] == pytest.approx(10.0)
    assert hurt["latency_p95_ms"] == pytest.approx(10.0)
    # a stall that holds 10 requests of 100 is the tail
    recs = [(i, 0.0, 0.5 if i < 10 else 0.01, 200, True) for i in range(100)]
    assert e2e.end_to_end(recs, 0.0, 1.0)["latency_p95_ms"] > 400


def test_qps_counts_good_answers_inside_the_window_only():
    recs = [(0, 0.0, 0.5, 200, True), (1, 0.1, 0.6, 200, False),
            (2, 0.9, 1.4, 200, True)]
    out = e2e.end_to_end(recs, 0.0, 1.0)
    assert out["qps"] == 1.0
    assert out["latency_p50_ms"] == pytest.approx(500.0)


# -- the layer readers on recorded snapshots ---------------------------------


def reader(name):
    return load(os.path.join(BENCH, "layers", name + ".py"), "t_" + name)


WINDOW = {
    "requests": 1000, "answers": 1000, "seconds": 10.0,
    "stages": {
        "admission_wait": {"count": 1000, "total_us": 5000.0},
        "parse": {"count": 1000, "total_us": 200000.0},
        "txn_open": {"count": 1000, "total_us": 30000.0},
        "plan": {"count": 1000, "total_us": 900000.0},
        "index_knn": {"count": 1000, "total_us": 800000.0},
        "stmt_eval": {"count": 1000, "total_us": 1500000.0},
        "stmt_envelope": {"count": 1000, "total_us": 130000.0},
        "device_rpc": {"count": 125, "total_us": 250000.0},
    },
    "batching": {"dispatches": 125, "riders": 1000},
    "before": {"runner": {"cc": {"misses": 7}}},
    "after": {"runner": {"cc": {"misses": 7},
                         "devices": [{"bytes_in_use": 77_000_000}]}},
    "config": {"rows": 100000, "dim": 128, "k": 10},
    "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "device": {"count": 1},
    "trace": {"busy_s": 0.06, "window_s": 3.0, "programs": {
        "jit_knn_rank_rescore": {"runs": 300, "seconds": 0.03}}},
}
# one run of the kernel for 8 riders: the bf16 copy, norms and mask once,
# 26 f32 candidates, the query and the answer of each rider
RUN_BYTES = 100000 * (128 * 2 + 4 + 1) + 8 * (26 * 128 * 4 + 128 * 4 + 80)


@pytest.mark.parametrize("name,want", [
    ("admission_wait_us", 5.0),
    ("txn_open_us", 30.0),
    ("parse_plan_us", 300.0),            # 200 + (900 - 800)
    ("stmt_eval_self_us", 700.0),        # (130 - 30) + (1500 - 900)
    ("index_knn_us", 800.0),
    ("riders_per_dispatch", 8.0),
    ("device_rpc_ms", 2.0),
    ("compiles_in_window", 0),
    ("device_bytes_in_use", 77_000_000),
    ("knn_rank_rescore_roofline", 100 * 300 * (RUN_BYTES / 819e9) / 0.03),
    ("device_idle_share", 98.0),
])
def test_layer_reader(name, want):
    assert reader(name).read(WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(
    f[:-3] for f in os.listdir(os.path.join(BENCH, "layers"))
    if f.endswith(".py")))
def test_layer_reader_with_nothing_to_read_returns_nothing(name):
    empty = dict(WINDOW, requests=0, answers=0, stages={}, trace=None,
                 peaks=None, batching={"dispatches": 0, "riders": 0},
                 after={"runner": {"cc": {"misses": 7},
                                   "devices": [{"bytes_in_use": None}]}})
    got = reader(name).read(empty)
    assert got is None or name == "compiles_in_window"


def test_parse_plan_without_a_parse_stage():
    """A bound-variable statement is served by the AST cache: no `parse`."""
    stages = {k: v for k, v in WINDOW["stages"].items() if k != "parse"}
    assert reader("parse_plan_us").read(dict(WINDOW, stages=stages)) \
        == pytest.approx(100.0)


def test_roofline_takes_the_larger_of_operations_and_bytes():
    costs = reader("knn_rank_rescore_roofline").costs
    ops, moved = costs(100000, 128, 10, 8.0)
    assert moved == RUN_BYTES
    assert ops == 2 * 8 * 100000 * 128 + 2 * 8 * 26 * 128
    # 8 riders: the pass is bound by memory; 4,096 riders: by the MXU
    assert moved / 819e9 > ops / 197e12
    ops, moved = costs(100000, 128, 10, 4096.0)
    assert ops / 197e12 > moved / 819e9
    # a trace without the program: nothing to read
    quiet = dict(WINDOW, trace={"busy_s": 0.1, "window_s": 3.0,
                                "programs": {"jit__descent_impl": {
                                    "runs": 5, "seconds": 0.1}}})
    assert reader("knn_rank_rescore_roofline").read(quiet) is None


def test_every_per_layer_metric_has_its_reader():
    for m in BENCHMARK["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layers", m["name"] + ".py"))


# -- the trace reduction -----------------------------------------------------


def test_union_and_gaps():
    ev = [(0, 10, "a"), (5, 20, "b"), (30, 40, "a"), (50, 60, "b")]
    assert reduce_.union_seconds([(s, e) for s, e, _n in ev]) \
        == pytest.approx(40e-9)
    assert dict(reduce_.gaps(ev)) == {"a -> b": pytest.approx(10e-9),
                                      "b -> a": pytest.approx(10e-9)}


def test_short_names():
    assert reduce_.short("%fusion.6 = (f32[8,896]{1,0}) fusion(f32[] %c)") \
        == "fusion.6"
    assert reduce_.short("jit_knn_rank_rescore(1234567)") \
        == "jit_knn_rank_rescore"
    assert reduce_.short("copy-done.1") == "copy-done.1"
