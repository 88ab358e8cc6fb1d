"""Faults planted under the timed path of kind `knn_rw`, for the proofs of
`correct`.

With this directory on PYTHONPATH and `BENCH_FAULT_RW=<mask|shift|sync>` set:

  mask   in the device runner, `VecStore.append` drops the mask bit of every
         fifth row it is handed (counted over all deltas): the row is on the
         chip and no search finds it -> `readback_missing`
  shift  in the device runner, `VecStore.append` writes every new row of a
         delta of 8 rows or fewer one place off (row r at r + 1): row r is
         missing and row r + 1 answers with its neighbour's vector ->
         `dist_err_max`, `readback_missing`. Set-up's 1,024-row delta goes
         where it belongs, so set-up's own check passes
  sync   in the serving process, `TpuVectorIndex._sync_impl` does nothing for
         every other version step (no search syncs to that version; the
         next step takes both in): searches ride without the rows their
         transactions can read -> `readback_missing`

`benchmark/run.py` never sets either; the benchmark's own runs do not come
here. (`tests/faults/` is kind `knn`'s hook, `tests/graph_faults/` kind
`graph`'s, `tests/scan_faults/` kind `scan`'s; all stay as they are.)
"""

import importlib.abc
import importlib.machinery
import os
import sys

TARGETS = {"mask": "surrealdb_tpu.device.vecstore",
           "shift": "surrealdb_tpu.device.vecstore",
           "sync": "surrealdb_tpu.idx.vector"}


def _cmdline_has_runner() -> bool:
    try:
        with open("/proc/self/cmdline", "rb") as f:
            return b"surrealdb_tpu.device.runner" in f.read()
    except OSError:
        return False


def _plant_mask(module):
    import numpy as np

    real = module.VecStore.append
    seen = [0]

    def append(self, rows, row_numbers, flags):
        flags = np.array(flags, np.uint8)
        for j in range(len(flags)):
            seen[0] += 1
            if seen[0] % 5 == 0:
                flags[j] = 0
        return real(self, rows, row_numbers, flags)

    module.VecStore.append = append
    print("[fault] vec_append drops every fifth row's mask bit",
          file=sys.stderr, flush=True)


def _plant_shift(module):
    import numpy as np

    real = module.VecStore.append

    def append(self, rows, row_numbers, flags):
        row_numbers = np.array(row_numbers, np.int32)
        if len(row_numbers) <= 8:
            new = row_numbers >= self.n
            row_numbers[new] = np.minimum(row_numbers[new] + 1,
                                          self.capacity - 1)
        return real(self, rows, row_numbers, flags)

    module.VecStore.append = append
    print("[fault] vec_append writes new rows one place off",
          file=sys.stderr, flush=True)


def _plant_sync(module):
    real = module.TpuVectorIndex._sync_impl
    seen = {"newest": -1, "steps": 0, "skipped": -1}

    def _sync_impl(self, ctx):
        from surrealdb_tpu import key as K

        ns, db, tb, ix = self.key
        ver = ctx.txn.get_val(K.ix_state(ns, db, tb, ix, b"vn")) or 0
        if self.version >= 0 and ver > seen["newest"]:
            seen["newest"] = ver
            seen["steps"] += 1
            if seen["steps"] > 8 and seen["steps"] % 2 == 0:
                seen["skipped"] = ver
        if ver == seen["skipped"]:
            return  # nobody syncs to this version: the next step does
        return real(self, ctx)

    module.TpuVectorIndex._sync_impl = _sync_impl
    print("[fault] every other index sync skips the log",
          file=sys.stderr, flush=True)


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
    fault = os.environ.get("BENCH_FAULT_RW")
    plant = {"mask": _plant_mask, "shift": _plant_shift,
             "sync": _plant_sync}.get(fault)
    if plant is None or (fault == "sync") == _cmdline_has_runner():
        return
    sys.meta_path.insert(0, _Finder(TARGETS[fault], plant))


_install()
