"""`VecStore.knn` on one device: one transfer in, one program, one copy
out. The batch is padded and cut into chunks on the host, the jitted
kernel takes the numpy batch, and distances and ids come back as one
packed int32 array (ops.topk pack_pairs). Answers and counts only; what
the launches cost is the chip's to say (PERF.md §6, PR 30).
"""

import numpy as np
import pytest

RIDERS = (1, 2, 3, 5, 9, 17, 32)
K = 10
DIM = 24
CFG = {"hbm_budget": 1 << 40, "score_budget": 1 << 26, "query_chunk": 8,
       "int8_oversample": 4, "block_rows": 1 << 20}
# branch -> (metric, cfg): the kernel `VecStore.ensure` picks on one device
BRANCHES = {
    "bf16": ("euclidean", CFG),
    "f32": ("manhattan", CFG),
    "int8": ("cosine", dict(CFG, hbm_budget=1)),
}


@pytest.fixture()
def one_device(monkeypatch):
    """The suite's 8 virtual devices would send `ensure` down the mesh
    branches; the served single-chip path is what is under test."""
    import jax

    monkeypatch.setattr(jax, "device_count", lambda: 1)


def make_store(branch, rows, live=None):
    from surrealdb_tpu.device.vecstore import VecStore

    metric, cfg = BRANCHES[branch]
    rng = np.random.default_rng(rows)
    xs = rng.normal(size=(rows, DIM)).astype(np.float32)
    valid = np.ones(rows, bool)
    if live is not None:
        valid[live:] = False
    st = VecStore(f"t/{branch}/{rows}", xs, valid, metric, 3.0, cfg)
    st.ensure()
    assert st.rank_mode == {"bf16": "bf16", "int8": "int8"}.get(branch)
    # the bf16 store grows in place: it is allocated for more rows than
    # it holds, and lets go of the rows it was shipped
    assert st.growable == (branch == "bf16") and st.shape == (rows, DIM)
    assert (st.vecs is None) == st.growable and st.capacity >= rows
    st.xs = xs
    return st


def by_hand(st, qvs, k):
    """The kernel called plainly on the batch padded and split by hand:
    (meta, bufs) as `VecStore.knn` has to return them."""
    import jax.numpy as jnp

    from surrealdb_tpu.device.vecstore import _pow2_chunks
    from surrealdb_tpu.ops import topk

    # the programs see the rows the arrays are allocated for
    n, b = st.capacity, qvs.shape[0]
    if st.rank_mode is None:
        dists, ids = topk.knn_search(
            st.device_vecs, jnp.asarray(qvs), k, st.metric, st.mink_p,
            st.device_valid)
        return ({"mode": "pairs", "rank_mode": None},
                [np.asarray(dists), np.asarray(ids)])
    budget = st.cfg["score_budget"] // (2 if st.rank_mode == "int8" else 1)
    bucket, chunk, r = _pow2_chunks(b, n, st.cfg["query_chunk"], budget)
    assert bucket >= b and r * chunk == bucket
    padded = np.zeros((bucket, DIM), np.float32)
    padded[:b] = qvs
    qs_r = jnp.asarray(padded).reshape(r, chunk, DIM)
    if st.rank_mode == "int8":
        kc = min(n, max(st.cfg["int8_oversample"] * k, k + 16))
        cand = topk.knn_rank_int8(
            st.device_rank, st.device_arow, st.device_x2, st.device_valid,
            qs_r, kc, st.metric)
        return ({"mode": "cand", "rank_mode": "int8", "kc": kc},
                [np.asarray(cand).reshape(bucket, kc)[:b]])
    kc = min(n, max(2 * k, k + 16))
    packed = np.asarray(topk.knn_rank_rescore(
        st.device_rank, st.device_full, qs_r, min(k, kc), kc, st.metric,
        st.device_x2, st.device_norms, st.device_valid))
    assert packed.dtype == np.int32 \
        and packed.shape == (r, chunk, 2 * min(k, kc))
    packed = packed.reshape(bucket, -1)[:b]
    return ({"mode": "pairs", "rank_mode": "bf16"},
            [packed[:, :min(k, kc)].view(np.float32),
             packed[:, min(k, kc):]])


@pytest.mark.parametrize("riders", RIDERS)
@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_knn_is_the_kernel_on_the_host_padded_batch(one_device, branch,
                                                    riders):
    # 6 live rows under k = 10: every answer also holds masked-out rows
    st = make_store(branch, rows=300, live=6)
    rng = np.random.default_rng(riders)
    qvs = st.xs[rng.integers(0, 6, riders)] \
        + rng.normal(size=(riders, DIM)).astype(np.float32) * 0.01
    meta, bufs = st.knn(qvs, K)
    want_meta, want = by_hand(st, qvs, K)
    assert meta == want_meta and len(bufs) == len(want)
    for got, ref in zip(bufs, want):
        assert got.flags.c_contiguous and got.dtype == ref.dtype
        assert got.shape == ref.shape and got.shape[0] == riders
        assert np.array_equal(got.view(np.int32), ref.view(np.int32))
    if meta["mode"] == "cand":
        (cand,) = bufs
        assert cand.dtype == np.int32 and cand.shape == (riders, meta["kc"])
        assert all(set(range(6)) <= set(row.tolist()) for row in cand)
        return
    dists, ids = bufs
    assert dists.dtype == np.float32 and ids.dtype == np.int32
    assert dists.shape == ids.shape == (riders, K)
    # the live rows first, nearest first, then the masked slots as inf
    assert np.isfinite(dists[:, :6]).all() and np.isinf(dists[:, 6:]).all()
    assert (np.diff(dists[:, :6], axis=1) >= 0).all()
    assert all(sorted(row[:6].tolist()) == list(range(6)) for row in ids)


def test_packed_pairs_carry_every_bit_pattern():
    """Distances and ids survive the packing bit for bit: infinities, a
    negative zero, NaN payloads, out-of-range ids, and ids whose bits
    read as f32 would be NaNs."""
    import jax
    import jax.numpy as jnp

    from surrealdb_tpu.ops.topk import pack_pairs, unpack_pairs

    d_bits = np.array([[0x7F800000, 0xFF800000, 0x80000000, 0x7FC00001],
                       [0x7FA00000, 0x00000001, 0x3F800000, 0xFFFFFFFF]],
                      np.uint32)
    ids = np.array([[-1, 2**31 - 1, -2**31, 0x7FC00001],
                    [0x7F800001, 0, 300, -2]], np.int64).astype(np.int32)
    packed = np.asarray(jax.jit(pack_pairs)(
        jnp.asarray(d_bits.view(np.float32)), jnp.asarray(ids)))
    assert packed.dtype == np.int32 and packed.shape == (2, 8)
    dists, back = unpack_pairs(packed)
    assert dists.dtype == np.float32 and back.dtype == np.int32
    assert np.array_equal(dists.view(np.uint32), d_bits)
    assert np.array_equal(back, ids)


@pytest.mark.parametrize("name", ["knn_search", "knn_search_blocked"])
def test_f32_search_packs_what_it_returns_as_a_pair(name):
    from surrealdb_tpu.ops import topk

    search = getattr(topk, name)
    rng = np.random.default_rng(3)
    xs = rng.normal(size=(700, DIM)).astype(np.float32)
    qs = rng.normal(size=(4, DIM)).astype(np.float32)
    valid = np.ones(700, bool)
    valid[5:] = False
    kw = {"block": 256} if name == "knn_search_blocked" else {}
    dists, ids = search(xs, qs, K, "euclidean", 3.0, valid, **kw)
    packed = np.asarray(
        search(xs, qs, K, "euclidean", 3.0, valid, packed=True, **kw))
    got_d, got_i = topk.unpack_pairs(packed)
    assert np.isinf(got_d[:, 5:]).all()
    assert np.array_equal(got_d.view(np.int32),
                          np.asarray(dists).view(np.int32))
    assert np.array_equal(got_i, np.asarray(ids))


@pytest.mark.parametrize("branch,kernel", [
    ("bf16", "jit(knn_rank_rescore)"), ("int8", "jit(knn_rank_int8)")])
def test_a_bucket_compiles_one_program_and_a_rider_count_none(
        one_device, branch, kernel):
    """jax's own compile accounting is the witness (`note_shape` never
    saw the eager `jit__pad` / `jit_reshape` programs that every new
    rider count used to compile): over rider counts 1..32 on a fresh
    store only the first count of each power-of-two bucket compiles,
    only the kernel, and a second pass compiles nothing."""
    from surrealdb_tpu.device import kernelstats

    kernelstats.install_jax_listeners()
    by_fn = kernelstats.COMPILE["backend_compile_s"]
    # rows no other test of this process uses: jit's cache is per process
    st = make_store(branch, rows={"bf16": 811, "int8": 821}[branch])
    before = dict(by_fn)
    compiled_at = []
    for b in range(1, 33):
        seen = dict(by_fn)
        st.knn(st.xs[:b], K)
        if by_fn != seen:
            compiled_at.append(b)
    assert compiled_at == [1, 2, 3, 5, 9, 17]
    new = {fn for fn in by_fn if by_fn[fn] != before.get(fn)}
    assert new == {kernel}, new
    after = dict(by_fn)
    for b in range(1, 33):
        st.knn(st.xs[:b], K)
    assert by_fn == after


def test_f32_search_compiles_nothing_beside_its_kernel(one_device):
    """The exact store's program is `exact_scan` whatever its size, and
    since PR 31 its batch is padded to a power-of-two bucket like the
    ranking branches': a program a bucket, none a rider count, and no
    program beside it."""
    from surrealdb_tpu.device import kernelstats

    kernelstats.install_jax_listeners()
    by_fn = kernelstats.COMPILE["backend_compile_s"]
    st = make_store("f32", rows=831)
    before = dict(by_fn)
    compiled_at = []
    for b in range(1, 10):
        seen = dict(by_fn)
        st.knn(st.xs[:b], K)
        if by_fn != seen:
            compiled_at.append(b)
    # `query_chunk` is 8 here: 9 riders are two rounds of the 8-program
    assert compiled_at == [1, 2, 3, 5]
    assert {fn for fn in by_fn if by_fn[fn] != before.get(fn)} \
        == {"jit(exact_scan)"}


def test_the_fault_hook_still_plants_its_kc(one_device, monkeypatch):
    """benchmark/tests/faults/sitecustomize.py replaces
    `ops.topk.knn_rank_rescore` on the module with a wrapper of this
    signature; `VecStore.knn` has to find it there at every call."""
    from surrealdb_tpu.ops import topk

    real = topk.knn_rank_rescore
    assert real.__name__ == "knn_rank_rescore"
    st = make_store("bf16", rows=300)
    qvs = st.xs[:3]
    _meta, (dists, ids) = st.knn(qvs, K)  # kc = 26, unplanted
    assert dists.shape == ids.shape == (3, K)
    calls = []

    def knn_rank_rescore(xs_rank, xs_full, qs_r, k, kc, *args, **kw):
        calls.append((np.shape(qs_r), k, kc))
        kc = min(kc, 4)
        return real(xs_rank, xs_full, qs_r, min(k, kc), kc, *args, **kw)

    monkeypatch.setattr(topk, "knn_rank_rescore", knn_rank_rescore)
    meta, (dists, ids) = st.knn(qvs, K)
    assert calls == [((1, 4, DIM), K, 26)]
    assert meta == {"mode": "pairs", "rank_mode": "bf16"}
    assert dists.shape == ids.shape == (3, 4)
    assert ids[:, 0].tolist() == [0, 1, 2] and np.isfinite(dists).all()


def test_the_program_keeps_the_name_the_roofline_looks_up():
    """benchmark/layers/knn_rank_rescore_roofline.py finds the program in
    the device trace as `jit_knn_rank_rescore`."""
    import jax
    import jax.numpy as jnp

    from surrealdb_tpu.ops.topk import knn_rank_rescore

    xs = jax.ShapeDtypeStruct((64, DIM), jnp.float32)
    lowered = knn_rank_rescore.lower(
        jax.ShapeDtypeStruct((64, DIM), jnp.bfloat16), xs,
        jax.ShapeDtypeStruct((1, 2, DIM), jnp.float32), 5, 21)
    assert "module @jit_knn_rank_rescore" in lowered.as_text()
    out = jax.eval_shape(
        lambda a, b, c: knn_rank_rescore(a, b, c, 5, 21),
        jax.ShapeDtypeStruct((64, DIM), jnp.bfloat16), xs,
        jax.ShapeDtypeStruct((1, 2, DIM), jnp.float32))
    assert out.shape == (1, 2, 10) and out.dtype == jnp.int32
