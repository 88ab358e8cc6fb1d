"""Kind `hybrid` and the cell `hybrid768.rrf-c32` (CPU, `--rehearsal` sizes;
not tier-1).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hybrid_kind.py -q

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
CELL = "hybrid768.rrf-c32"
NEW_LAYERS = ("ft_search_us", "ft_post_ms", "ft_build_s", "ft_bm25_roofline")


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


hybrid = load(os.path.join(BENCH, "kinds", "hybrid.py"), "t_hybrid")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
with open(os.path.join(BENCH, "configs", "hybrid768.json")) as f:
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


def test_the_cell_is_correct_and_refuses_the_control():
    out, res = run_cell("--seed", "2147484101", "--seconds", "2",
                        "--trace", "0", "--control")
    assert out.returncode == 0, out.stderr[-3000:]
    assert res["rehearsal"] is True and res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "latency_p50_ms", "latency_p95_ms",
                                   "recall_at_10", "setup_s"}
    c = res["compared"]
    assert {"bad_answers", "ft_mismatch", "ft_score_err_max",
            "dist_err_max", "vs_recall_at_10", "fused_mismatch",
            "recall_at_10", "host_served_events", "device_dispatches",
            "ft_device_dispatches", "ft_host_searches", "ft_riders_off",
            "failed_requests"} <= set(c)
    assert c["ft_riders_off"]["value"] == 0
    assert c["ft_host_searches"]["value"] == 0
    assert list(res)[-1] == "compared"
    # the BM25 leg in bf16, no rescore, in the program's place
    assert res["control"]["correct"] is False
    assert not res["control"]["ft_score_err_max"]["ok"]
    assert res["control"]["bad_answers"]["ok"]


def test_the_traced_cell_prints_the_new_metrics():
    out, res = run_cell("--seed", "7", "--seconds", "3", "--trace", "1")
    assert out.returncode == 0, out.stderr[-3000:]
    assert res["correct"] is True
    m = res["metrics"]
    assert m["ft_search_us"]["value"] > 0 and m["ft_post_ms"]["value"] > 0
    assert m["ft_build_s"]["value"] > 0
    # the leg's stage holds the full-text batcher's wait and ride
    assert m["ft_search_us"]["value"] < m["batch_wait_us"]["value"] \
        + m["batch_ride_us"]["value"] + 2 * m["request_edge_us"]["value"]
    # no device plane on the CPU: the roofline is a chip run's
    assert "ft_bm25_roofline" not in m
    listed = {p["name"] for p in BENCHMARK["per_layer"]
              if "workloads" not in p or CELL in p["workloads"]}
    assert set(m) <= listed and set(NEW_LAYERS) <= listed


@pytest.mark.parametrize("fault,says,number", [
    # the reference's count of rows decides first: a leg with a row less
    # (most queries match one passage) or one that matches no query's
    # every word is no answer
    ("drop", "each rider's best passage dropped", "bad_answers"),
    ("or", "AND evaluated as OR", "bad_answers"),
    ("idf", "idf not clamped", "ft_score_err_max"),
])
def test_a_fault_under_the_timed_path_is_not_correct(fault, says, number):
    out, res = run_cell("--seed", "2147485007", "--seconds", "2",
                        "--trace", "0", BENCH_FAULT_FT=fault,
                        PYTHONPATH=os.path.join(HERE, "hybrid_faults"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"[fault] {says}" in out.stderr
    assert res["correct"] is False
    assert not res["compared"][number]["ok"]
    assert res["compared"]["ft_device_dispatches"]["ok"]


# -- the pieces, without a server ----------------------------------------------


def test_the_runner_probe_refuses_a_program_without_the_op():
    class Sup:
        def __init__(self, status):
            self.status = status

        def runner_status(self):
            return self.status

    assert hybrid.runner_ft(Sup({"ft": {"riders": 0}})) == {"riders": 0}
    for status in ({}, {"ft": None}, {"scan": {"riders": 1}}):
        with pytest.raises(hybrid.SetupFailed, match="no BM25 op"):
            hybrid.runner_ft(Sup(status))


def test_the_bulk_route_writes_the_programs_postings():
    sys.path.insert(0, ROOT)
    try:
        from surrealdb_tpu import key as K
        from surrealdb_tpu.kvs.api import deserialize
    finally:
        sys.path.remove(ROOT)
    ps = hybrid.Passages(300, 1 << 20, 20, 8, 3)
    ridk = np.frombuffer(b"".join(K.enc_value(i) for i in range(300)),
                         np.uint8).reshape(300, -1)
    ranks, values = hybrid.posting_values(ps, ridk)
    texts = ps.texts()
    for j in (0, len(values) // 2, len(values) - 1):
        word = hybrid.words_of_ranks(ps, [ranks[j]])[0]
        want = {}
        for p, text in enumerate(texts):
            at, offs = 0, []
            for w in text.split(" "):
                if w == word:
                    offs.append((0, at, at + len(w), at, at + len(w)))
                at += len(w) + 1
            if offs:
                want[K.enc_value(p)] = (len(offs), offs, p)
        got = deserialize(values[j])
        assert got == want and list(got) == list(want)


def test_the_reference_is_plain_bm25():
    ps = hybrid.Passages(500, 200, 12, 8, 9)
    ref = hybrid.Reference(ps, 1.2, 0.75)
    words = ps.words_of(17)[:3]
    docs, s, matches = ref.top(words, 10)
    k1, b = float(np.float32(1.2)), float(np.float32(0.75))
    toks = [ps.ranks[ps.starts[p]:ps.starts[p] + ps.lens[p]].tolist()
            for p in range(ps.n)]
    avg = sum(map(len, toks)) / ps.n
    want = []
    for p, t in enumerate(toks):
        if not all(w in t for w in words):
            continue
        score = 0.0
        for w in words:
            df = sum(w in x for x in toks)
            idf = max(np.log((ps.n - df + 0.5) / (df + 0.5)), 0.0)
            tfp = 1 + np.log(t.count(w))
            norm = (1 - b) + b / avg * len(t)
            score += idf * (k1 + 1) * tfp / (tfp + k1 * norm) if idf else 0
        want.append((-score, p))
    want.sort()
    assert matches == len(want) and 17 in docs
    assert docs.tolist() == [p for _s, p in want[:10]]
    assert np.allclose(s, [-x for x, _p in want[:10]], rtol=1e-13)


def tiny(n=600):
    ps = hybrid.Passages(n, 400, 14, 8, 5)
    rng = np.random.default_rng(2)
    xs = rng.standard_normal((n, 16)).astype(np.float32)
    target = rng.integers(0, n, 40)
    words = [ps.words_of(p)[:2] for p in target.tolist()]
    qs = xs[target] + 0.01
    sz = dict(CONFIG, rows=n, dim=16)
    return hybrid.Deployment(sz, xs, ps, target, words, qs, {})


def reference_answers(dep):
    ref_i, ref_d = hybrid.knn.brute_force(dep.xs, dep.qs, "cosine", 10)
    out = []
    for i in range(len(dep.qs)):
        docs, s, _m = dep.ref.top(dep.words[i], 10)
        ft = [(int(d), float(np.float32(v))) for d, v in zip(docs, s)]
        vs = list(zip(ref_i[i].tolist(), ref_d[i].tolist()))
        fused = hybrid.rrf([[v for v, _d in vs], [d for d, _s in ft]], 10,
                           60)
        out.append((i, {"vs": vs, "ft": ft, "fused": fused}))
    return out


LIMITS = dict(CONFIG["limits"], compare_max=1000)


def test_compare_accepts_the_reference():
    dep = tiny()
    says = []
    got = hybrid.compare(dep, reference_answers(dep), LIMITS, says.append)
    assert all(c["ok"] for c in got.values()) and not says, says
    assert got["recall_at_10"]["value"] == 1.0
    assert got["ft_score_err_max"]["value"] <= 2.0 ** -23


@pytest.mark.parametrize("alter,number", [
    (lambda a: dict(a, ft=a["ft"][1:] + [(a["ft"][0][0] + 1, 1.0)]),
     "ft_mismatch"),
    (lambda a: dict(a, ft=[(a["ft"][0][0], a["ft"][0][1] * 1.001)]
                    + a["ft"][1:]), "ft_score_err_max"),
    (lambda a: dict(a, fused=a["fused"][::-1]), "fused_mismatch"),
])
def test_one_altered_answer_is_counted(alter, number):
    dep = tiny()
    answers = reference_answers(dep)
    i = next(i for i, a in answers if len(a["ft"]) >= 3
             and a["fused"][0][1] != a["fused"][-1][1])
    answers[i] = (i, alter(answers[i][1]))
    says = []
    got = hybrid.compare(dep, answers, LIMITS, says.append)
    assert not got[number]["ok"] and says


def test_compare_counts_what_is_no_answer():
    dep = tiny()
    answers = reference_answers(dep)

    def reply(vs, ft, fused):
        return json.dumps({"id": 1, "result": [
            {"status": "OK", "result": None},
            {"status": "OK", "result": None},
            {"status": "OK", "result": {
                "vs": [{"id": f"passage:{v}", "d": d} for v, d in vs],
                "ft": [{"id": f"passage:{v}", "s": s} for v, s in ft],
                "fused": [{"id": f"passage:{v}", "rrf_score": s}
                          for v, s in fused]}}]}).encode()

    a = answers[0][1]
    ok = hybrid.parse_answer(200, reply(a["vs"], a["ft"], a["fused"]),
                             "passage")
    assert ok == a
    answers[1] = (1, hybrid.parse_answer(503, b"busy", "passage"))
    answers[2] = (2, hybrid.parse_answer(200, reply(
        a["vs"][:9], a["ft"], a["fused"]), "passage"))
    answers[3] = (3, dict(answers[3][1], ft=answers[3][1]["ft"][:-1]))
    says = []
    got = hybrid.compare(dep, answers, LIMITS, says.append)
    assert got["bad_answers"]["value"] == 3
    assert "status 503" in says[0] and "9 rows" in answers[2][1]


def test_device_served_needs_a_rider_a_request():
    sup = {c: 0 for c in hybrid.knn.COUNTERS}

    def snap(ops, ft_ops, riders, host=0):
        return {"supervisor": dict(sup, state="ready", ft_host_searches=host),
                "runner": {"ops": {"ann_search": ops, "ft_bm25": ft_ops},
                           "ft": {"riders": riders}}}

    before, says = snap(5, 3, 40), []
    got = hybrid.device_served(before, snap(9, 8, 72), 32, says.append)
    assert all(c["ok"] for c in got.values()) and not says
    got = hybrid.device_served(before, snap(9, 8, 70, host=2), 32,
                               says.append)
    assert got["ft_riders_off"]["value"] == 2
    assert not got["ft_host_searches"]["ok"]


# -- the new readers -----------------------------------------------------------


WINDOW = {
    "requests": 1000, "answers": 1000, "seconds": 10.0,
    "config": {"rows": 400_000, "k": 10, "words": 6},
    "stages": {"ft_search": {"count": 1000, "total_us": 50_000_000.0},
               "ft_post": {"count": 100, "total_us": 200_000.0}},
    "batching": {"dispatches": 300, "riders": 3000},
    "before": {"stages": {"ft_build": {"count": 1, "total_ms": 21_500.0}},
               "runner": {"ft": {"riders": 500, "dispatches": 60,
                                 "candidates": 1_000, "postings_read":
                                 10_000}}},
    "after": {"runner": {"ft": {"riders": 1500, "dispatches": 160,
                                "candidates": 101_000,
                                "postings_read": 10_010_000}}},
    "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "trace": {"busy_s": 0.9, "window_s": 3.0, "programs": {
        "jit__bm25_impl": {"runs": 30, "seconds": 0.3}}},
}
# 100 calls: 10 riders, 1,000 candidates and 100,000 postings read a call
RUN_BYTES = 4.0 * 100_000 + 8.0 * 1_000 + 4.0 * 10 * (1 + 32 * 9)


@pytest.mark.parametrize("name,want", [
    ("ft_search_us", 50_000.0),
    ("ft_post_ms", 2.0),
    ("ft_build_s", 21.5),
    ("ft_bm25_roofline", 100 * 30 * (RUN_BYTES / 819e9) / 0.3),
])
def test_layer_reader(name, want):
    assert reader(name).read(WINDOW) == pytest.approx(want)


def test_the_roofline_counts_the_dense_rows_read():
    """Riders on the dense rung read whole tf rows: `dense_bytes`, where
    the runner counts it, is moved too (41 MB a call here)."""
    ft = dict(WINDOW["after"]["runner"]["ft"], dense_bytes=4_100_000_000)
    window = dict(WINDOW, after={"runner": {"ft": ft}})
    assert reader("ft_bm25_roofline").read(window) == pytest.approx(
        100 * 30 * ((RUN_BYTES + 41_000_000) / 819e9) / 0.3)


@pytest.mark.parametrize("name", NEW_LAYERS)
def test_layer_reader_with_nothing_to_read_returns_nothing(name):
    """A program from before this cell has no such stage, counter or
    program: the reader returns nothing and does not raise."""
    empty = dict(WINDOW, stages={}, trace={"busy_s": 0.1, "window_s": 3.0,
                                          "programs": {}},
                 before={"supervisor": {}, "runner": {"cc": {}}},
                 after={"supervisor": {}, "runner": {"cc": {}}})
    assert reader(name).read(empty) is None
    assert reader(name).read(dict(empty, trace=None, peaks=None)) is None


def test_the_benchmark_gained_the_cells_and_readers_and_nothing_else():
    by = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW_LAYERS:
        assert by[name]["workloads"] == [CELL]
    assert [m["name"] for m in BENCHMARK["per_layer"]][-4:] \
        == list(NEW_LAYERS)
    assert [w["name"] for w in BENCHMARK["workloads"]][-2:] \
        == [CELL, "graph3hop.hop-c1"]
    entry = BENCHMARK["configs"][-1]
    assert entry["name"] == "hybrid768" and entry["reduced"] == ["rows"]
    assert CONFIG["rows"] != CONFIG["published"]["rows"]
    assert BENCHMARK["run_seconds"] == 30
