"""The served kernels compiled at the benchmark's real sizes for a TPU that
is described and not attached (one chip of a v5e 2x2): what the chip's
compiler would refuse, it refuses here, at no chip time. Nothing runs, so
nothing here is a time or a result. The topology is described inside a
fixture and only this file does it (one process at a time may load the
TPU's library)."""

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("riders", [1, 32])
def test_exact_scan_reads_a_million_rows_in_place(one_chip, no_compile_cache,
                                                  riders):
    """`scan768`'s program at 1,000,000 x 768 (not a multiple of the
    65,536-row block): it compiles for one v5e chip, and beside its
    3.07 GB argument it needs a few MB: the store is neither padded nor
    copied nor normalised into a second array."""
    import jax
    import jax.numpy as jnp

    from surrealdb_tpu import cnf
    from surrealdb_tpu.ops import topk

    n, dim, k = 1_000_000, 768, 10
    xs = jax.ShapeDtypeStruct((n, dim), jnp.float32, sharding=one_chip)
    qs = jax.ShapeDtypeStruct((riders, dim), jnp.float32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    compiled = topk.exact_scan.lower(
        xs, qs, k, "cosine", 3.0, valid, cnf.KNN_BLOCK_ROWS).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= n * dim * 4
    assert mem.temp_size_in_bytes < 256 << 20, mem.temp_size_in_bytes
    assert mem.output_size_in_bytes < 1 << 20


@pytest.mark.parametrize("metric,rows", [("euclidean", 1), ("euclidean", 1024),
                                         ("cosine", 4)])
def test_vec_append_writes_in_place_at_the_cells_capacity(
        one_chip, no_compile_cache, metric, rows):
    """`exact128rw`'s write at the capacity 100,000 rows are allocated
    (106,496 x 128): the donating scatter compiles for one v5e chip under
    the name the benchmark's roofline reader looks up, every output
    aliases its argument (no second copy of the 82 MB block), and it needs
    next to nothing beside them."""
    import jax
    import jax.numpy as jnp

    from surrealdb_tpu.device.vecstore import _append_program, capacity_for

    cap, dim = capacity_for(100_000), 128
    assert cap == 106_496

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = _append_program(metric).lower(
        arr((cap, dim), jnp.float32), arr((cap, dim), jnp.bfloat16),
        arr((cap,), jnp.float32), arr((cap,), jnp.bool_),
        arr((rows, dim + 3), jnp.int32))
    assert "module @jit_vec_append" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    block = cap * dim * 6 + cap * 5
    assert mem.argument_size_in_bytes >= block
    assert mem.alias_size_in_bytes >= block
    assert mem.temp_size_in_bytes < 8 << 20, mem.temp_size_in_bytes


def test_knn_rank_rescore_compiles_at_the_cells_capacity(one_chip,
                                                         no_compile_cache):
    """The search program over the allocated rows (106,496, a multiple
    of 8,192) at the 32-rider bucket: compiles for one v5e chip, its
    score block [32, 106,496] f32 and little else beside the arguments."""
    import jax
    import jax.numpy as jnp

    from surrealdb_tpu.ops import topk

    cap, dim, k, kc = 106_496, 128, 10, 26

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = topk.knn_rank_rescore.lower(
        arr((cap, dim), jnp.bfloat16), arr((cap, dim), jnp.float32),
        arr((1, 32, dim), jnp.float32), k, kc, "euclidean",
        arr((cap,), jnp.float32), None, arr((cap,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes
    assert mem.output_size_in_bytes < 1 << 16  # [1, 32, 2k] int32, tiled
