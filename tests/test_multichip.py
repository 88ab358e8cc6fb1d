"""Multi-chip dryrun coverage: run dryrun_multichip(8) in a subprocess with an
8-virtual-device CPU mesh (the driver validates multi-chip the same way), and
exercise the sharded KNN path end-to-end in-process.

Reference role: core/src/idx/trees/knn.rs:15 (cross-shard top-k merge) /
SURVEY §2.13 (sharded query fan-out).
"""

import os
import subprocess
import sys

import numpy as np


def test_dryrun_multichip_subprocess():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    code = (
        "import __graft_entry__ as g; g.dryrun_multichip(8); print('MC_OK')"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-4000:]}"
    assert "MC_OK" in proc.stdout


def test_sharded_knn_mesh():
    import jax

    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    from surrealdb_tpu.parallel.mesh import default_mesh, shard_rows, sharded_knn

    rng = np.random.default_rng(3)
    xs = rng.normal(size=(512, 32)).astype(np.float32)
    qs = rng.normal(size=(4, 32)).astype(np.float32)
    mesh = default_mesh(jax.devices()[:8])
    xs_sharded, pad = shard_rows(mesh, xs)
    valid = np.zeros(xs_sharded.shape[0], dtype=bool)
    valid[: xs.shape[0]] = True
    d, i = sharded_knn(mesh, xs_sharded, qs, valid, k=5, metric="euclidean")
    d, i = np.asarray(d), np.asarray(i)
    ref = np.linalg.norm(xs[None, :, :] - qs[:, None, :], axis=-1)
    want_i = np.argsort(ref, axis=1)[:, :5]
    want_d = np.sort(ref, axis=1)[:, :5]
    np.testing.assert_allclose(np.sort(d, axis=1), want_d, rtol=2e-3, atol=2e-3)
    for b in range(qs.shape[0]):
        assert set(i[b].tolist()) == set(want_i[b].tolist())


def test_sharded_rank_rescore_kernel():
    """Production two-stage sharded kernel (bf16 rank + local f32 rescore +
    ICI candidate merge) matches exact numpy KNN."""
    import jax
    from surrealdb_tpu.parallel.mesh import (
        default_mesh, shard_rows, shard_vec, sharded_rank_rescore,
    )

    rng = np.random.default_rng(7)
    xs = rng.normal(size=(4096, 64)).astype(np.float32)
    qs = rng.normal(size=(8, 64)).astype(np.float32)
    mesh = default_mesh(jax.devices()[:8])
    for metric in ("euclidean", "cosine"):
        full, pad = shard_rows(mesh, xs)
        if metric == "cosine":
            norms = np.maximum(np.linalg.norm(xs, axis=1, keepdims=True), 1e-30)
            rank, _ = shard_rows(mesh, (xs / norms).astype(np.float32))
            rank = rank.astype("bfloat16")
            x2 = None
            nv = shard_vec(mesh, norms[:, 0].astype(np.float32), pad, 1.0)
        else:
            rank, _ = shard_rows(mesh, xs)
            rank = rank.astype("bfloat16")
            x2 = shard_vec(mesh, (xs.astype(np.float64) ** 2).sum(1).astype(np.float32), pad)
            nv = None
        valid = shard_vec(mesh, np.ones(xs.shape[0], bool), pad)
        d, i = sharded_rank_rescore(mesh, rank, full, qs, 10, 40, metric, x2, nv, valid)
        d, i = np.asarray(d), np.asarray(i)
        if metric == "euclidean":
            ref = np.linalg.norm(xs[None, :, :] - qs[:, None, :], axis=-1)
        else:
            xn = xs / np.maximum(np.linalg.norm(xs, axis=1, keepdims=True), 1e-30)
            qn = qs / np.maximum(np.linalg.norm(qs, axis=1, keepdims=True), 1e-30)
            ref = 1.0 - qn @ xn.T
        want_i = np.argsort(ref, axis=1)[:, :10]
        # recall@10 must be >= 0.95; exact distances for recalled ids
        hits = sum(len(set(i[b]) & set(want_i[b])) for b in range(8))
        assert hits / 80 >= 0.95, f"{metric} recall {hits/80}"
        np.testing.assert_allclose(
            np.sort(d, axis=1)[:, :8],
            np.sort(ref, axis=1)[:, :8], rtol=5e-3, atol=5e-3)


def test_tpu_vector_index_sharded_1m():
    """TpuVectorIndex (the product path, not the raw kernel) engages the
    sharded bf16 rank/rescore on a >=1M-row store over the 8-device mesh;
    recall@10 >= 0.95 vs exact; tombstones excluded."""
    import jax
    from surrealdb_tpu.idx.vector import TpuVectorIndex
    from surrealdb_tpu.val import RecordId

    assert jax.device_count() >= 8
    n, dim, k = 1_000_000, 32, 10
    rng = np.random.default_rng(11)
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    ix = TpuVectorIndex("t", "t", "pts", "ix", {"dimension": dim, "distance": "cosine", "vector_type": "f32"})
    ix.vecs = xs
    ix.valid = np.ones(n, dtype=bool)
    ix.valid[::97] = False  # tombstones
    ix.rids = [RecordId("pts", i) for i in range(n)]
    ix.version = 0  # pretend synced
    q = rng.normal(size=(dim,)).astype(np.float32)
    pairs = ix._raw_knn(q, k)
    # device blocks live runner-side now: introspect through the inline
    # supervisor's store (conftest pins SURREAL_DEVICE=inline)
    from surrealdb_tpu.device import get_supervisor

    st = get_supervisor().inline_store(ix._dev_key)
    assert st is not None and st.mesh is not None \
        and st.device_rank is not None, "sharded rank path not engaged"
    assert ix.rank_mode == "bf16"
    assert len(pairs) == k
    got = {r.id for r, _ in pairs}
    assert not any(i % 97 == 0 for i in got), "tombstoned row returned"
    xn = xs / np.maximum(np.linalg.norm(xs, axis=1, keepdims=True), 1e-30)
    ref = 1.0 - xn @ (q / max(np.linalg.norm(q), 1e-30))
    ref[~ix.valid] = np.inf
    want = set(np.argsort(ref)[:k].tolist())
    assert len(got & want) / k >= 0.95


def test_sharded_to_int8_transition_requeries():
    """Regression (ADVICE r3, high): a sharded bf16 store whose post-update
    rebuild crosses KNN_HBM_BUDGET_BYTES must re-dispatch as int8 — stale
    self.mesh used to route to sharded_rank_rescore with device_full=None."""
    import jax
    from surrealdb_tpu import cnf
    from surrealdb_tpu.idx.vector import TpuVectorIndex
    from surrealdb_tpu.val import RecordId

    assert jax.device_count() >= 8
    n, dim, k = 4096, 16, 5
    rng = np.random.default_rng(3)
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    ix = TpuVectorIndex(
        "t", "t", "pts", "ix",
        {"dimension": dim, "distance": "cosine", "vector_type": "f32"},
    )
    ix.vecs = xs
    ix.valid = np.ones(n, dtype=bool)
    ix.rids = [RecordId("pts", i) for i in range(n)]
    ix.version = 0
    q = rng.normal(size=(dim,)).astype(np.float32)
    first = ix._raw_knn(q, k)
    from surrealdb_tpu.device import get_supervisor

    assert get_supervisor().inline_store(ix._dev_key).mesh is not None
    assert ix.rank_mode == "bf16"
    old = cnf.KNN_HBM_BUDGET_BYTES
    cnf.KNN_HBM_BUDGET_BYTES = 6 * n * dim // 16  # force int8 on rebuild
    try:
        ix._drop_device()  # what update()/_rebuild() do
        assert ix.rank_mode is None  # cache epoch bumped: re-ship next
        second = ix._raw_knn(q, k)
        assert ix.rank_mode == "int8"
        assert get_supervisor().inline_store(ix._dev_key).mesh is None
    finally:
        cnf.KNN_HBM_BUDGET_BYTES = old
    assert [r.id for r, _ in first] == [r.id for r, _ in second]


def test_multihost_hier_mesh_matches_ground_truth():
    """(dcn, data) hybrid mesh: hierarchical two-stage merge returns the
    exact top-k (VERDICT r4 item 5 — multi-host mesh code validated on
    the virtual device grid)."""
    import numpy as np

    from surrealdb_tpu.parallel.mesh import (
        multihost_mesh, shard_rows_hier, shard_vec_hier,
        sharded_rank_rescore_hier,
    )

    m = multihost_mesh(hosts=2)
    assert m.devices.shape[0] == 2 and m.axis_names == ("dcn", "data")
    rng = np.random.default_rng(11)
    xs = rng.normal(size=(2048, 48)).astype(np.float32)
    qs = rng.normal(size=(6, 48)).astype(np.float32)
    xf, pad = shard_rows_hier(m, xs)
    x2 = shard_vec_hier(
        m, (xs.astype(np.float64) ** 2).sum(1).astype(np.float32), pad)
    valid = shard_vec_hier(m, np.ones(len(xs), bool), pad, fill=False)
    d, i = sharded_rank_rescore_hier(
        m, xf.astype("bfloat16"), xf, qs, k=10, kc=60,
        metric="euclidean", x2=x2, valid=valid)
    d, i = np.asarray(d), np.asarray(i)
    ref = np.linalg.norm(xs[None, :, :] - qs[:, None, :], axis=-1)
    want = np.argsort(ref, axis=1)[:, :10]
    recall = np.mean([
        len(set(i[b].tolist()) & set(want[b].tolist())) / 10
        for b in range(6)
    ])
    assert recall >= 0.95, recall
    # distances ascend and match the exact values for the hits
    assert all((np.diff(d[b]) >= -1e-6).all() for b in range(6))
