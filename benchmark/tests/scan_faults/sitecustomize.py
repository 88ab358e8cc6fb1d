"""Faults planted under the timed path of kind `scan`, for the proofs of
`correct`.

With this directory on PYTHONPATH and `BENCH_FAULT_SCAN=<host|bf16>` set:

  host   in the serving process, every fifth scan after the first eight is
         scored by the host's numpy in the device's place, and nothing counts
         it (`exec/stream.py scan_on_device` says no, silently): set-up's two
         scans pass its checks, the window's riders fall short of its requests
  bf16   in the device runner, the exact store's program
         (`ops/topk.py exact_scan`) ranks every dispatch of two riders or
         more by one bfloat16 pass (rows normalised and rounded to bf16, f32
         sums, exact top-k of that, no rescore): set-up's scans ride alone,
         the window's batches lose members of the f64 top 10

`benchmark/run.py` never sets either; the benchmark's own runs do not come
here. (`tests/faults/` is kind `knn`'s hook, `tests/graph_faults/` kind
`graph`'s; both stay as they are.)
"""

import importlib.abc
import importlib.machinery
import os
import sys

TARGETS = {"host": "surrealdb_tpu.exec.stream", "bf16": "surrealdb_tpu.ops.topk"}


def _cmdline_has_runner() -> bool:
    try:
        with open("/proc/self/cmdline", "rb") as f:
            return b"surrealdb_tpu.device.runner" in f.read()
    except OSError:
        return False


def _plant_host(module):
    real = module.scan_on_device
    calls = [0]

    def scan_on_device(col, metric):
        calls[0] += 1
        if calls[0] > 8 and calls[0] % 5 == 0:
            return False
        return real(col, metric)

    module.scan_on_device = scan_on_device
    print("[fault] every fifth scan scored on the host, uncounted",
          file=sys.stderr, flush=True)


def _plant_bf16(module):
    import jax
    import jax.numpy as jnp

    real = module.exact_scan

    @jax.jit
    def ranked_in_bf16(xs, qs, valid):
        xn = xs / jnp.maximum(jnp.linalg.norm(xs, axis=-1, keepdims=True),
                              1e-30)
        qn = qs / jnp.maximum(jnp.linalg.norm(qs, axis=-1, keepdims=True),
                              1e-30)
        sims = jnp.einsum("nd,bd->bn", xn.astype(jnp.bfloat16),
                          qn.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        return jnp.where(valid[None, :], 1.0 - sims, jnp.inf)

    def exact_scan(xs, qs, k, metric, p, valid, block_rows):
        if metric != "cosine" or qs.shape[0] < 2:
            return real(xs, qs, k, metric, p, valid, block_rows)
        d, i = module.top_k_smallest(ranked_in_bf16(xs, qs, valid), k)
        return module.pack_pairs(d, i)

    module.exact_scan = exact_scan
    print("[fault] exact_scan ranks in bfloat16", file=sys.stderr, flush=True)


class _Loader(importlib.abc.Loader):
    def __init__(self, inner, plant):
        self.inner, self.plant = inner, plant

    def create_module(self, spec):
        return self.inner.create_module(spec)

    def exec_module(self, module):
        self.inner.exec_module(module)
        self.plant(module)


class _Finder(importlib.abc.MetaPathFinder):
    def __init__(self, target, plant):
        self.target, self.plant = target, plant

    def find_spec(self, name, path, target=None):
        if name != self.target:
            return None
        spec = importlib.machinery.PathFinder.find_spec(name, path)
        if spec is not None:
            spec.loader = _Loader(spec.loader, self.plant)
        return spec


def _install():
    fault = os.environ.get("BENCH_FAULT_SCAN")
    if fault == "host" and not _cmdline_has_runner():
        sys.meta_path.insert(0, _Finder(TARGETS[fault], _plant_host))
    elif fault == "bf16" and _cmdline_has_runner():
        sys.meta_path.insert(0, _Finder(TARGETS[fault], _plant_bf16))


_install()
