"""Faults planted under the timed path of kind `graph`, for the proofs of
`correct`.

With this directory on PYTHONPATH and `BENCH_FAULT_BAG=<drop|swap|dedup>`
set, the device runner (and no other process) alters what
`device/csrstore.py CsrStore.bag_hop` returns for every rider with an answer
in a dispatch of two riders or more (set-up's own traversals ride alone, so
its checks pass and the fault meets the window, where 32 callers batch):

  drop   the rider's last id is left out (its last total one less)
  swap   the rider's first id becomes another node's
  dedup  the rider's ids each once, first occurrences kept: the SET answer

`benchmark/run.py` never sets either; the benchmark's own runs do not come
here. (`tests/faults/` is kind `knn`'s hook and stays as it is.)
"""

import importlib.abc
import importlib.machinery
import os
import sys

TARGET = "surrealdb_tpu.device.csrstore"


def _cmdline_has_runner() -> bool:
    try:
        with open("/proc/self/cmdline", "rb") as f:
            return b"surrealdb_tpu.device.runner" in f.read()
    except OSError:
        return False


def _alter(fault: str, ids, n_nodes: int):
    import numpy as np

    if not len(ids):
        return ids
    if fault == "drop":
        return ids[:-1]
    if fault == "swap":
        out = ids.copy()
        out[0] = (out[0] + 1) % n_nodes
        return out
    _seen, first = np.unique(ids, return_index=True)
    return ids[np.sort(first)]


def _plant(module, fault: str):
    import numpy as np

    real = module.CsrStore.bag_hop

    def bag_hop(self, packed, caps):
        totals, flat = real(self, packed, caps)
        if len(packed) < 2:
            return totals, flat
        totals, parts, at = totals.copy(), [], 0
        for j, tot in enumerate(totals.tolist()):
            if any(n > c for n, c in zip(tot, caps)):
                continue        # an overflowed rider has no ids here
            ids = _alter(fault, flat[at:at + tot[-1]], self.n_nodes)
            at += tot[-1]
            totals[j, -1] = len(ids)
            parts.append(ids)
        flat = np.concatenate(parts) if parts else flat[:0]
        return totals, np.ascontiguousarray(flat, np.int32)

    module.CsrStore.bag_hop = bag_hop
    print(f"[fault] bag_hop answers altered: {fault}", file=sys.stderr,
          flush=True)


class _Loader(importlib.abc.Loader):
    def __init__(self, inner, fault):
        self.inner, self.fault = inner, fault

    def create_module(self, spec):
        return self.inner.create_module(spec)

    def exec_module(self, module):
        self.inner.exec_module(module)
        _plant(module, self.fault)


class _Finder(importlib.abc.MetaPathFinder):
    def __init__(self, fault):
        self.fault = fault

    def find_spec(self, name, path, target=None):
        if name != TARGET:
            return None
        spec = importlib.machinery.PathFinder.find_spec(name, path)
        if spec is not None:
            spec.loader = _Loader(spec.loader, self.fault)
        return spec


def _install():
    fault = os.environ.get("BENCH_FAULT_BAG")
    if fault in ("drop", "swap", "dedup") and _cmdline_has_runner():
        sys.meta_path.insert(0, _Finder(fault))


_install()
