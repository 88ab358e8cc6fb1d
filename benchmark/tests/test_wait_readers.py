"""The readers of the waits' stages (CPU; not tier-1): the five hand-offs of
a device RPC, the two mutexes of `txn_open`, the stall watch's two, and the
two spans that had no reader, each on a recorded window with values chosen by
hand, and on a window of a program that records none of them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_wait_readers.py -q
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


# a window of 1,000 requests in 125 dispatches from a program that has none
# of the stages (the parent of PR 35 has the last two)
BARE = {
    "requests": 1000, "answers": 1000, "seconds": 10.0,
    "stages": {"device_rpc": st(125, 250000.0),
               "rpc_out": st(125, 100000.0),
               "rpc_back": st(125, 50000.0),
               "txn_open": st(1000, 4000000.0)},
    "batching": {"dispatches": 125, "riders": 1000},
    "before": {"stages": {}}, "after": {"stages": {}},
}

# the same window from a program that records them all: 300 ticks of the
# watch, one stall of 1.3 s, 40 lost commits
WINDOW = dict(BARE, stages=dict(
    BARE["stages"],
    rpc_send_wake=st(125, 62500.0), rpc_send=st(125, 12500.0),
    rpc_wire_out=st(125, 25000.0), rpc_recv=st(125, 37500.0),
    rpc_wake=st(125, 12500.0),
    txn_lock_ds=st(1010, 3000000.0), txn_lock_store=st(1010, 500000.0),
    gil_wake=st(300, 45000.0), request_stall=st(1, 1300000.0),
    reply_encode=st(1000, 21000.0), commit_retry=st(40, 1800000.0)))

EXPECTED = {
    "rpc_send_wake_ms": 0.5, "rpc_send_ms": 0.1, "rpc_wire_out_ms": 0.2,
    "rpc_recv_ms": 0.3, "rpc_wake_ms": 0.1,
    "txn_lock_ds_us": 3000.0, "txn_lock_store_us": 500.0,
    "gil_wake_us": 150.0, "request_stall_ms": 1300.0,
    "reply_encode_us": 21.0, "commit_retry_ms": 1.8,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_layer_reader_of_a_wait(name):
    read = reader(name).read
    assert read(WINDOW) == pytest.approx(EXPECTED[name])
    assert read(BARE) is None


def test_layer_parts_sum_to_the_stage_that_holds_them():
    got = {name: reader(name).read(WINDOW) for name in EXPECTED}
    assert got["rpc_send_wake_ms"] + got["rpc_send_ms"] \
        + got["rpc_wire_out_ms"] == pytest.approx(
            reader("rpc_out_ms").read(WINDOW))
    assert got["rpc_recv_ms"] + got["rpc_wake_ms"] == pytest.approx(
        reader("rpc_back_ms").read(WINDOW))
    assert got["txn_lock_ds_us"] + got["txn_lock_store_us"] \
        <= reader("txn_open_us").read(WINDOW)


def test_layer_request_stall_is_a_number_whenever_the_watch_ran():
    sound = dict(WINDOW, stages={k: v for k, v in WINDOW["stages"].items()
                                 if k != "request_stall"})
    assert reader("request_stall_ms").read(sound) == 0.0
    assert reader("gil_wake_us").read(sound) == 150.0


def test_layer_commit_retry_reads_zero_where_only_the_window_lost_none():
    quiet = dict(WINDOW, stages={k: v for k, v in WINDOW["stages"].items()
                                 if k != "commit_retry"})
    read = reader("commit_retry_ms").read
    assert read(quiet) is None  # a program that never recorded the stage
    seen = dict(quiet, after={"stages": {
        "commit_retry": {"count": 12, "total_ms": 400.0}}})
    assert read(seen) == 0.0


def test_layer_every_new_metric_is_declared_with_its_reader():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    declared = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    # appended after what was there (later PRs append after these)
    last_before = names.index("vec_append_roofline")
    assert all(names.index(name) > last_before for name in EXPECTED)
    for name in EXPECTED:
        m = declared[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
        # every cell runs them, but the one only a writing cell records
        assert m.get("workloads") == (
            ["exact128rw.rw95-c32"] if name == "commit_retry_ms" else None)
