"""The readers of the dispatch timeline's stages and counters (CPU; not
tier-1), each on a recorded window with values chosen by hand, and on a
window of the parent's shape, whose program records none of them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "t_" + name, os.path.join(BENCH, "layers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def st(count, total_us):
    return {"count": count, "total_us": total_us}


# what the parent's program gives a window of 1,000 requests in 125
# dispatches: the stages it records, `runner_status()` without `loop`/`ann`
PARENT = {
    "requests": 1000, "answers": 1000, "seconds": 10.0,
    "stages": {
        "admission_wait": st(1000, 5000.0),
        "parse": st(1000, 20000.0),
        "plan": st(1000, 900000.0),
        "index_knn": st(1000, 800000.0),
        "stmt_eval": st(1000, 1500000.0),
        "stmt_envelope": st(1000, 130000.0),
        "device_rpc": st(125, 250000.0),
    },
    "batching": {"dispatches": 125, "riders": 1000},
    "before": {"stages": {"index_knn": {"count": 9, "total_ms": 7.0}},
               "runner": {"cc": {"misses": 7}}},
    "after": {"stages": {"index_knn": {"count": 1009, "total_ms": 807.0}},
              "runner": {"cc": {"misses": 7}}},
    "config": {"rows": 400000, "dim": 768, "k": 10},
    "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "device": {"count": 1},
    "trace": {"busy_s": 0.12, "window_s": 3.0, "programs": {
        "jit__descent_impl": {"runs": 50, "seconds": 0.1}}},
}

# the same window from this PR's program
WINDOW = dict(
    PARENT,
    stages=dict(
        PARENT["stages"],
        request=st(1000, 2000000.0),
        batch_wait=st(1000, 300000.0),
        batch_ride=st(1000, 480000.0),
        batch_dispatch=st(125, 400000.0),
        knn_post=st(125, 50000.0),
        rpc_out=st(125, 100000.0),
        runner_h2d=st(125, 5000.0),
        runner_device=st(125, 25000.0),
        runner_d2h=st(125, 2500.0),
        runner_other=st(125, 17500.0),
        rpc_back=st(125, 100000.0),
    ),
    before={"stages": {"request": {"count": 50, "total_ms": 90.0,
                                   "cpu_ms": 40.0}},
            "runner": {"cc": {"misses": 7},
                       "loop": {"idle_ns": 1_000, "busy_ns": 500},
                       "ann": {"searches": 10, "rows_scored": 1_000_000}}},
    after={"stages": {"request": {"count": 1050, "total_ms": 2090.0,
                                  "cpu_ms": 840.0}},
           "runner": {"cc": {"misses": 7},
                      "loop": {"idle_ns": 9_000_001_000,
                               "busy_ns": 1_000_000_500},
                      "ann": {"searches": 135,
                              "rows_scored": 201_000_000}}},
)
# 125 searches scored 200e6 rows: 1.6e6 rows a search, each 768 int8
# values, an f32 scale and the int32 id that named it
SEARCH_BYTES = 1.6e6 * (768 + 4 + 4)

WANT = [
    ("request_edge_us", 345.0),     # (2000 - 5 - 20 - 130 - 1500) ms / 1000
    ("request_cpu_us", 800.0),      # (840 - 40) ms / 1000
    ("batch_wait_us", 300.0),
    ("batch_ride_us", 480.0),
    ("batch_host_ms", 1.2),         # (400 - 250) ms / 125
    ("knn_post_ms", 0.4),
    ("rpc_out_ms", 0.8),
    ("rpc_back_ms", 0.8),
    ("runner_host_ms", 0.2),        # (5 + 2.5 + 17.5) ms / 125
    ("runner_device_ms", 0.2),
    ("runner_idle_share", 90.0),    # 9 s idle of 10 s
    ("ann_descent_roofline", 100 * 50 * (SEARCH_BYTES / 819e9) / 0.1),
]


@pytest.mark.parametrize("name,want", WANT)
def test_reader_on_a_recorded_window(name, want):
    assert reader(name).read(WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n, _w in WANT])
def test_reader_on_the_parents_window_reads_nothing(name):
    assert reader(name).read(PARENT) is None


def test_the_rpc_parts_add_up_to_the_rpc():
    """The four per-layer readings of one RPC partition `device_rpc_ms`."""
    parts = sum(reader(n).read(WINDOW) for n in (
        "rpc_out_ms", "runner_host_ms", "runner_device_ms", "rpc_back_ms"))
    assert parts == pytest.approx(reader("device_rpc_ms").read(WINDOW))


def test_wait_and_ride_stay_inside_index_knn():
    inside = reader("batch_wait_us").read(WINDOW) \
        + reader("batch_ride_us").read(WINDOW)
    assert inside <= reader("index_knn_us").read(WINDOW)


def test_request_edge_without_a_parse_stage():
    """A bound-variable statement is served by the AST cache: no `parse`."""
    stages = {k: v for k, v in WINDOW["stages"].items() if k != "parse"}
    assert reader("request_edge_us").read(dict(WINDOW, stages=stages)) \
        == pytest.approx(365.0)


def test_request_cpu_when_the_first_snapshot_had_no_request():
    before = {"stages": {}, "runner": WINDOW["before"]["runner"]}
    assert reader("request_cpu_us").read(dict(WINDOW, before=before)) \
        == pytest.approx(840.0)


def test_the_descent_roofline_needs_the_program_and_the_counter():
    quiet = dict(WINDOW, trace={"busy_s": 0.1, "window_s": 3.0, "programs": {
        "jit_knn_rank_rescore": {"runs": 5, "seconds": 0.1}}})
    assert reader("ann_descent_roofline").read(quiet) is None
    still = dict(WINDOW, after=WINDOW["before"])
    assert reader("ann_descent_roofline").read(still) is None
    # counted at what the answers need: under 100 % by a wide margin here
    assert 0 < reader("ann_descent_roofline").read(WINDOW) < 100


def test_every_new_metric_is_declared_and_only_appended():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    assert names[-len(WANT):] == [n for n, _w in WANT]
    roof = per_layer[-1]
    assert roof["workloads"] == ["ann768.knn-c32"]
    assert all("workloads" not in m for m in per_layer[-len(WANT):-1])
