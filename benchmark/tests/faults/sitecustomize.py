"""A fault planted under the timed path, for the proofs of `correct`.

With this directory on PYTHONPATH and `BENCH_FAULT_KC=<n>` set, the device
runner (and no other process) runs `ops.topk.knn_rank_rescore` with `n`
ranking candidates in place of the store's kc = max(2k, k+16): the step a
later PR would take to buy speed. `benchmark/run.py` never sets either; the
benchmark's own runs do not come here.
"""

import importlib.abc
import importlib.machinery
import os
import sys

TARGET = "surrealdb_tpu.ops.topk"


def _cmdline_has_runner() -> bool:
    try:
        with open("/proc/self/cmdline", "rb") as f:
            return b"surrealdb_tpu.device.runner" in f.read()
    except OSError:
        return False


def _plant(module, kc_fault: int):
    real = module.knn_rank_rescore

    def knn_rank_rescore(xs_rank, xs_full, qs_r, k, kc, *args, **kw):
        kc = min(kc, kc_fault)
        return real(xs_rank, xs_full, qs_r, min(k, kc), kc, *args, **kw)

    module.knn_rank_rescore = knn_rank_rescore
    print(f"[fault] knn_rank_rescore runs with kc <= {kc_fault}",
          file=sys.stderr, flush=True)


class _Loader(importlib.abc.Loader):
    def __init__(self, inner, kc_fault):
        self.inner, self.kc_fault = inner, kc_fault

    def create_module(self, spec):
        return self.inner.create_module(spec)

    def exec_module(self, module):
        self.inner.exec_module(module)
        _plant(module, self.kc_fault)


class _Finder(importlib.abc.MetaPathFinder):
    def __init__(self, kc_fault):
        self.kc_fault = kc_fault

    def find_spec(self, name, path, target=None):
        if name != TARGET:
            return None
        spec = importlib.machinery.PathFinder.find_spec(name, path)
        if spec is not None:
            spec.loader = _Loader(spec.loader, self.kc_fault)
        return spec


def _install():
    kc = os.environ.get("BENCH_FAULT_KC")
    if kc and _cmdline_has_runner():
        sys.meta_path.insert(0, _Finder(int(kc)))


_install()
