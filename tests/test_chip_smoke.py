"""chip_smoke.py on the CPU: three subprocess runs side by side — a
rehearsal at tiny sizes (report and last-line verdict schema, cache
placed by the environment), a rehearsal whose first check is made to fail, and the
plain command, which without a TPU must fail and print no result."""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(args, cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    # the suite's own device settings must not leak into the smoke
    for k in ("SURREAL_DEVICE", "SURREAL_KNN_HOST_BATCH", "XLA_FLAGS"):
        env.pop(k, None)
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + args,
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )


def test_chip_smoke_rehearsal_break_check_and_no_chip(tmp_path):
    runs = {
        "ok": _spawn(["--rehearsal", "--seed", "3"], tmp_path / "a"),
        "broken": _spawn(["--rehearsal", "--break-check", "exact"],
                         tmp_path / "b"),
        "plain": _spawn([], tmp_path / "c"),
    }
    done = {}
    try:
        for name, p in runs.items():
            out, err = p.communicate(timeout=240)
            done[name] = (p.returncode, out, err)
    finally:
        for p in runs.values():
            if p.poll() is None:
                p.kill()
    # a failed check, and a missing chip, fail the run with no result
    rc, out, err = done["broken"]
    assert rc != 0 and out.strip() == "", (rc, out, err[-2000:])
    assert "CheckFailed: exact" in err
    rc, out, err = done["plain"]
    assert rc != 0 and out.strip() == "", (rc, out, err[-2000:])
    assert "not a TPU" in err
    rc, out, err = done["ok"]
    assert rc == 0, err[-4000:]
    lines = out.strip().splitlines()
    assert len(lines) == 2, out[-2000:]
    # the last line is the verdict, with these keys and no others
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    res = json.loads(lines[0])
    assert res["rehearsal"] is True and res["seed"] == 3
    assert set(res["versions"]) == {"jax", "jaxlib", "libtpu"}
    for stage in ("exact", "ann", "graph", "ml"):
        assert res["stages"][stage]["checked"] is True
    # approx_max_k is exact on the CPU backend: every id matches
    assert res["stages"]["exact"]["ids_matching"] \
        == res["stages"]["exact"]["ids_expected"]
    assert res["stages"]["exact"]["inserted_rows_read_back"] >= 1
    assert res["stages"]["ann"]["recall_at_10"] >= 0.95
    assert res["stages"]["graph"]["device_hops"] >= 1
    assert set(res["setup_s"]) == {"ingest", "index_sync", "graph_build",
                                   "ship", "compile"}
    runner = res["runner"]
    assert all(runner["dispatches"][op] >= 1
               for op in ("vec_knn", "ann_search", "csr_hop"))
    assert runner["blocks"] == {"vec": 1, "ann": 1, "csr": 1}
    assert runner["rank_modes"] == ["bf16"] and runner["mesh_ndev"] == 1
    assert len(runner["device_bytes_in_use"]) == 1
    assert not any(res["supervisor"].values())
    assert res["memtable"] in ("native", "python")
    # the environment placed the cache, and the kernels landed in it
    cc = res["compile_cache"]
    assert cc["dir"] == str(tmp_path / "a")
    assert cc["entries_before"] == 0 and cc["entries_after"] >= 3
    assert cc["compiled"] >= 3 and res["compile_s_by_kernel"]
