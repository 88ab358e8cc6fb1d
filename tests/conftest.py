"""Test config: force an 8-device virtual CPU mesh so sharding paths are
exercised without TPU hardware (the driver dry-runs multichip the same way).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# device ops run in-process by default under pytest: the suite already
# initializes jax on CPU, and inline mode keeps cnf/jax monkeypatching
# effective for the kernel-selection tests. The chaos suite
# (test_device_chaos.py) installs real subprocess supervisors itself.
os.environ.setdefault("SURREAL_DEVICE", "inline")
# keep the device kernels under test: the production router
# (SURREAL_KNN_HOST_BATCH=auto) would host-route every dispatch on the
# suite's CPU-platform inline supervisor, and the kernel-selection /
# multichip / chaos suites exist to exercise the device path. The
# batcher suite overrides per-test to cover the host routing.
os.environ.setdefault("SURREAL_KNN_HOST_BATCH", "device")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# imported here, under the environment above, so the runner-side
# placement helpers (which only look at an already-imported jax) see
# the 8-device mesh from the first test on
import jax  # noqa: E402,F401
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running soak/chaos tests (tier-1 skips)"
    )


@pytest.fixture()
def ds():
    """Datastore under test. SURREAL_TEST_BACKEND=remote runs every
    fixture-based test against the distributed KV service (a fresh
    server per test — the storage contract is what's being swapped,
    reference SURVEY §4: distribution is tested through the storage
    contract)."""
    from surrealdb_tpu import Datastore

    if os.environ.get("SURREAL_TEST_BACKEND") == "remote":
        from surrealdb_tpu.kvs.remote import serve_kv

        srv = serve_kv("127.0.0.1", 0, block=False)
        d = Datastore(f"remote://127.0.0.1:{srv.server_address[1]}")
        yield d
        d.close()
        srv.shutdown()
        return
    d = Datastore("memory")
    yield d
    d.close()


@pytest.fixture()
def q(ds):
    def run(sql, **vars):
        return ds.query(sql, ns="test", db="test", vars=vars or None)

    return run


@pytest.fixture()
def q1(ds):
    def run(sql, **vars):
        return ds.query_one(sql, ns="test", db="test", vars=vars or None)

    return run
