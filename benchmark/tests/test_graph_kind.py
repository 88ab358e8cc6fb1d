"""Kind `graph` and the cell `graph3hop.hop-c32` (CPU, `--rehearsal` sizes;
not tier-1).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_graph_kind.py -q

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
CELL = "graph3hop.hop-c32"
NEW_LAYERS = ("graph_hop_us", "hop_post_ms", "csr_bag_overflows",
              "csr_bag_hop_roofline")


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


graph = load(os.path.join(BENCH, "kinds", "graph.py"), "t_graph")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)


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
    assert res["metrics"]["recall_at_10"]["value"] == 1.0
    c = res["compared"]
    assert {"bad_answers", "bag_mismatch", "order_mismatch",
            "readback_missing", "readback_queries", "host_served_events",
            "device_dispatches", "failed_requests"} <= set(c)
    assert c["device_dispatches"]["value"] >= 1
    assert c["readback_queries"]["value"] >= 1
    assert c["ordered_answers"]["value"] >= 1
    assert list(res)[-1] == "compared"
    # the SET answer in the program's place
    assert res["control"]["correct"] is False
    assert res["control"]["bag_mismatch"]["value"] > 0
    assert res["control"]["recall_at_10"]["value"] < 1.0


def test_the_traced_cell_prints_the_graph_engines_metrics():
    out, res = run_cell("--seed", "7", "--seconds", "3", "--trace", "1")
    assert out.returncode == 0, out.stderr[-3000:]
    assert res["correct"] is True
    m = res["metrics"]
    assert m["graph_hop_us"]["value"] > 0 and m["graph_hop_us"]["unit"] == "us"
    assert m["hop_post_ms"]["value"] > 0
    assert m["csr_bag_overflows"] == {"value": 0, "unit": "count"}
    assert m["compiles_in_window"]["value"] == 0
    # the chain is the batcher's client: its stages hold the ride
    assert m["graph_hop_us"]["value"] >= m["batch_ride_us"]["value"]
    # the CPU backend's trace has no device plane, so no program's
    # device seconds: the roofline share is a chip run's
    assert "csr_bag_hop_roofline" not in m
    listed = {p["name"] for p in BENCHMARK["per_layer"]
              if "workloads" not in p or CELL in p["workloads"]}
    assert set(m) <= listed and set(NEW_LAYERS) <= listed


@pytest.mark.parametrize("fault,numbers", [
    ("drop", ("bag_mismatch", "recall_at_10")),
    ("swap", ("bag_mismatch", "recall_at_10")),
    ("dedup", ("bag_mismatch", "recall_at_10")),
])
def test_a_fault_under_the_timed_path_is_not_correct(fault, numbers):
    """The runner's `bag_hop` altered for every rider of a dispatch of
    two or more (graph_faults/): set-up's lone traversals pass its own
    checks, the window's batches carry the fault to the clients."""
    out, res = run_cell("--seed", "2147485007", "--seconds", "2",
                        "--trace", "0", BENCH_FAULT_BAG=fault,
                        PYTHONPATH=os.path.join(HERE, "graph_faults"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"[fault] bag_hop answers altered: {fault}" in out.stderr
    assert res["correct"] is False
    for name in numbers:
        assert not res["compared"][name]["ok"], name
    assert res["compared"]["bad_answers"]["ok"]
    assert res["compared"]["device_dispatches"]["ok"]


# -- the comparison and its control, without a server --------------------------


def tiny(seed=5, n=400, e=4000, n_sql=16, pool=200):
    src, dst, rng = graph.edges_from(seed, n, e)
    ref = graph.Reference(n, src[:e - n_sql], dst[:e - n_sql])
    tail = list(zip(src[e - n_sql:].tolist(), dst[e - n_sql:].tolist()))
    ref.add_sql(tail)
    starts = np.concatenate([np.asarray([a for a, _b in tail] * 4),
                             rng.choice(n, pool - 4 * n_sql, replace=False)])
    sz = {"nodes": n, "edges": e, "hops": 3, "sql_edges": n_sql,
          "runner_op": "csr_bag_hop"}
    return graph.Deployment(sz, ref, starts, {}), tail


LIMITS = {"bag_mismatch": 0, "order_mismatch": 0, "recall_at_10_min": 1.0,
          "compare_max": 1000}


def reference_answers(dep):
    return [(i, dep.ref.walk(int(s), 3)[0]) for i, s in enumerate(dep.pool)]


def test_compare_accepts_the_reference_and_refuses_the_control():
    dep, _tail = tiny()
    says = []
    got = graph.compare(dep, reference_answers(dep), LIMITS, says.append)
    assert all(c["ok"] for c in got.values()) and not says
    assert got["recall_at_10"]["value"] == 1.0
    assert got["readback_queries"]["value"] >= 64
    assert 1 <= got["ordered_answers"]["value"] < len(dep.pool)
    control = [(i, graph.control_answer(a))
               for i, a in reference_answers(dep)]
    got = graph.compare(dep, control, LIMITS, says.append)
    assert not got["bag_mismatch"]["ok"] and says
    assert got["bag_mismatch"]["value"] > len(dep.pool) // 2
    assert got["recall_at_10"]["value"] < 1.0
    assert got["bad_answers"]["ok"]


def test_the_reference_walks_plain_lists_in_edge_order():
    src = np.array([0, 0, 1, 2, 0, 1])
    dst = np.array([1, 2, 2, 0, 1, 3])
    ref = graph.Reference(4, src, dst)
    assert ref.adj == [[1, 2, 1], [2, 3], [0], []]
    assert ref.walk(0, 1) == ([1, 2, 1], True)
    assert ref.walk(0, 2) == ([2, 3, 0, 2, 3], True)
    assert ref.walk(3, 3) == ([], True)
    ref.add_sql([(1, 0)])
    # the SQL edge's place among node 1's edges is the server's to say
    assert ref.walk(0, 2) == ([2, 3, 0, 0, 2, 3, 0], False)
    assert ref.walk(2, 1) == ([0], True)
    assert graph.control_answer([2, 3, 0, 2, 3]) == [0, 2, 3]


@pytest.mark.parametrize("alter,number", [
    (lambda a: a[:-1], "bag_mismatch"),                       # one id dropped
    (lambda a: [(a[0] + 1) % 400] + a[1:], "bag_mismatch"),   # one id swapped
    (lambda a: a[1:2] + a[0:1] + a[2:], "order_mismatch"),    # two ids turned
])
def test_one_altered_answer_is_counted(alter, number):
    dep, _tail = tiny()
    answers = reference_answers(dep)
    # an answer whose order is known and whose first two ids differ
    i = next(i for i, a in answers
             if dep.ref.walk(int(dep.pool[i]), 3)[1] and len(a) > 2
             and a[0] != a[1])
    answers[i] = (i, alter(answers[i][1]))
    says = []
    got = graph.compare(dep, answers, LIMITS, says.append)
    assert got[number]["value"] == 1 and not got[number]["ok"]
    assert f"query {i} " in says[0]
    if number == "order_mismatch":
        assert got["bag_mismatch"]["ok"]
        assert got["recall_at_10"]["value"] == 1.0


def test_a_lost_path_through_an_sql_edge_is_not_read_back():
    dep, tail = tiny()
    answers = reference_answers(dep)
    from collections import Counter

    # a path end that is reached through the SQL edge alone: take one
    # such id out of the answer and that edge was not traversed whole
    for a, b in tail:
        i = next(i for i, s in enumerate(dep.pool) if s == a)
        through = Counter(x for b2 in dep.ref.sql_adj[a]
                          for x in dep.ref.walk(b2, 2)[0])
        have = Counter(answers[i][1])
        only = [x for x in through if have[x] == through[x]]
        if only:
            break
    ids = list(answers[i][1])
    ids.remove(only[0])
    answers[i] = (i, ids)
    says = []
    got = graph.compare(dep, answers, LIMITS, says.append)
    assert got["readback_missing"]["value"] == 1
    assert any("did not come back" in t for t in says)


def test_compare_counts_what_is_no_answer():
    dep, _tail = tiny()
    answers = reference_answers(dep)
    ok = json.dumps({"id": 1, "result": [{"status": "OK", "result": [
        ["person:3", "person:9"]]}]}).encode()
    assert graph.parse_answer(200, ok) == [3, 9]
    answers[3] = (3, graph.parse_answer(503, b"busy"))
    answers[4] = (4, graph.parse_answer(200, ok.replace(b"person:9",
                                                        b"knows:9")))
    answers[5] = (5, graph.parse_answer(200, json.dumps(
        {"id": 5, "error": {"code": -32000, "message": "no"}}).encode()))
    answers[6] = (6, [7, 400])                 # an id outside the table
    answers[7] = (7, graph.parse_answer(200, json.dumps({"id": 1, "result": [
        {"status": "OK", "result": []}]}).encode()))   # no row at all
    says = []
    got = graph.compare(dep, answers, LIMITS, says.append)
    assert got["bad_answers"]["value"] == 5 and not got["bad_answers"]["ok"]
    assert "status 503" in says[0]
    assert "another table" in answers[4][1] and "rpc error" in answers[5][1]
    assert "unreadable" in answers[7][1]


def test_requests_bind_the_start_record():
    sz = {"hops": 3}
    req = json.loads(graph.rpc_body(sz, 7, np.int64(123456)))
    text, variables = req["params"]
    assert graph.PATH == "/rpc" and req["method"] == "query" \
        and req["id"] == 7
    assert text == ("SELECT VALUE ->knows->person->knows->person->knows"
                    "->person FROM type::record('person', $i)")
    assert variables == {"i": 123456} and "123456" not in text
    with open(os.path.join(BENCH, "configs", "graph3hop.json")) as f:
        assert json.load(f)["statement"] == text


def test_device_served_needs_quiet_counters_and_a_moving_op():
    sup = {c: 0 for c in graph.COUNTERS}
    before = {"supervisor": dict(sup, state="ready"),
              "runner": {"ops": {"csr_bag_hop": 5}}}
    after = {"supervisor": dict(sup, state="ready"),
             "runner": {"ops": {"csr_bag_hop": 9}}}
    got = graph.device_served(before, after, "csr_bag_hop", lambda _t: None)
    assert all(c["ok"] for c in got.values())
    routed = {"supervisor": dict(sup, state="ready", host_routed=2),
              "runner": {"ops": {"csr_bag_hop": 9}}}
    says = []
    got = graph.device_served(before, routed, "csr_bag_hop", says.append)
    assert not got["host_served_events"]["ok"] and "host_routed" in says[0]
    got = graph.device_served(before, before, "csr_bag_hop", says.append)
    assert not got["device_dispatches"]["ok"]


# -- the new readers -----------------------------------------------------------


WINDOW = {
    "requests": 1000, "answers": 1000, "seconds": 10.0,
    "stages": {"graph_hop": {"count": 1000, "total_us": 90_000_000.0},
               "hop_post": {"count": 100, "total_us": 25_000.0}},
    "batching": {"dispatches": 100, "riders": 1000},
    "before": {"runner": {"csr": {"bag_riders": 50, "paths_out": 50_000,
                                  "edges_gathered": 55_500,
                                  "overflows": 1}}},
    "after": {"runner": {"csr": {"bag_riders": 1050, "paths_out": 1_050_000,
                                 "edges_gathered": 1_165_500,
                                 "overflows": 3}}},
    "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "trace": {"busy_s": 0.06, "window_s": 3.0, "programs": {
        "jit__bag_hop_impl": {"runs": 30, "seconds": 0.03}}},
}
# 1,000 riders: 1,110,000 paths over the levels, 1,000,000 of them the
# last's, so 1,000 + 110,000 frontier entries; a dispatch is 10 riders
RUN_BYTES = 4 * (2 * 111_000 + 2 * 1_110_000) / 100


@pytest.mark.parametrize("name,want", [
    ("graph_hop_us", 90_000.0),
    ("hop_post_ms", 0.25),
    ("csr_bag_overflows", 2),
    ("csr_bag_hop_roofline", 100 * 30 * (RUN_BYTES / 819e9) / 0.03),
])
def test_layer_reader(name, want):
    assert reader(name).read(WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW_LAYERS)
def test_layer_reader_with_nothing_to_read_returns_nothing(name):
    """A program from before this cell has no such stage, counter or
    program: the reader returns nothing and does not raise."""
    empty = dict(WINDOW, stages={}, trace={"busy_s": 0.1, "window_s": 3.0,
                                          "programs": {}},
                 before={"runner": {"cc": {"misses": 7}}},
                 after={"runner": {"cc": {"misses": 7}}})
    assert reader(name).read(empty) is None
    assert reader(name).read(dict(empty, trace=None, peaks=None,
                                  batching={"dispatches": 0,
                                            "riders": 0})) is None


def test_the_roofline_counts_what_the_answers_need():
    moved = reader("csr_bag_hop_roofline").moved
    # one rider, levels of 10, 100 and 1,000 paths: 111 frontier entries
    # (the start, 10, 100), 1,110 column reads and as many id writes
    assert moved(1, 1110, 1000) == 4 * (2 * 111 + 2 * 1110)


def test_the_accepted_entries_changed_only_by_their_cells():
    """The entries that read nothing in a graph cell list the KNN cells;
    the rest of the accepted benchmark is as it was."""
    knn_cells = ["exact128.knn-c32", "ann768.knn-c32", "exact128.knn-c1"]
    by = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in ("index_knn_us", "knn_post_ms", "parse_plan_us"):
        assert by[name]["workloads"] == knn_cells
    for name in NEW_LAYERS:
        assert by[name]["workloads"] == [CELL]
    assert [w["name"] for w in BENCHMARK["workloads"]] \
        == knn_cells + [CELL]
    assert BENCHMARK["run_seconds"] == 30
