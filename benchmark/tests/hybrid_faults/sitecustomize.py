"""Faults planted under the timed path of kind `hybrid`, for the proofs of
`correct`.

With this directory on PYTHONPATH and `BENCH_FAULT_FT=<drop|or|idf>` set:

  drop  in the device runner, `device/ftstore.py FtStore.bm25` answers every
        rider of a call of two riders or more as if the posting of its best
        passage had been dropped: that passage leaves its places, the rest
        move up, the count is one less
  or    in the device runner, the program `_bm25_impl` keeps a candidate
        that lacks some of the rider's words (`_and` ignores them): AND
        evaluated as OR over the rarest word's passages
  idf   in the serving process, `idx/ftdevice.py _idf` is not clamped at 0:
        a word in more than half the passages weighs less than nothing

Set-up's first search rides alone and finds the passage its words came
from, so set-up passes and the window meets the fault. `benchmark/run.py`
never sets either; the benchmark's own runs do not come here.
"""

import importlib.abc
import importlib.machinery
import math
import os
import sys

TARGETS = {"drop": "surrealdb_tpu.device.ftstore",
           "or": "surrealdb_tpu.device.ftstore",
           "idf": "surrealdb_tpu.idx.ftdevice"}


def _cmdline_has_runner() -> bool:
    try:
        with open("/proc/self/cmdline", "rb") as f:
            return b"surrealdb_tpu.device.runner" in f.read()
    except OSError:
        return False


def _plant_drop(module):
    import numpy as np

    real = module.FtStore.bm25

    def bm25(self, packed, consts, cap, k):
        out = real(self, packed, consts, cap, k)
        if len(packed) < 2:
            return out
        t = (packed.shape[1] - 1) // 2
        for row in out:
            if row[0] <= 0:
                continue
            row[0] -= 1
            for at in [1, 1 + k, 1 + 2 * k] + [1 + (3 + j) * k
                                               for j in range(t)]:
                row[at:at + k - 1] = row[at + 1:at + k].copy()
                row[at + k - 1] = -1 if at == 1 else 0
            row[1 + k + k - 1] = np.float32(-np.inf).view(np.int32)
        return out

    module.FtStore.bm25 = bm25
    print("[fault] each rider's best passage dropped", file=sys.stderr,
          flush=True)


def _plant_or(module):
    module._and = lambda matched, found, used: matched
    print("[fault] AND evaluated as OR", file=sys.stderr, flush=True)


def _plant_idf(module):
    module._idf = lambda n_docs, df: math.log(
        (n_docs - df + 0.5) / (df + 0.5))
    print("[fault] idf not clamped", file=sys.stderr, flush=True)


PLANT = {"drop": _plant_drop, "or": _plant_or, "idf": _plant_idf}


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
    fault = os.environ.get("BENCH_FAULT_FT")
    if fault not in PLANT:
        return
    in_runner = _cmdline_has_runner()
    if in_runner == (fault != "idf"):
        sys.meta_path.insert(0, _Finder(TARGETS[fault], PLANT[fault]))


_install()
