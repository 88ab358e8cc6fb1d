"""The ten check_robustness rules, ported onto the shared parse.

Same semantics and message text as the historical per-rule scanner —
the conformance gate and the rule-7/8 unit tests key off these strings
— but run against `FileInfo` objects parsed exactly once, with stable
`Finding` identities so the baseline machinery covers them too.

Rule ids: bare-except, thread-daemon, stream-deadline, twopc-swallow,
jax-import, seam, notify, knn, mem-account, follower. The rename-proof
existence assertions (rules 7-10) are preserved verbatim: deleting or
renaming a policed function is itself a finding.
"""

from __future__ import annotations

import ast
import os
import re

from .core import FileInfo, Finding

# files + function-name shape that rule 4 (2PC decision paths) covers
_TWOPC_FILES = ("surrealdb_tpu/kvs/shard.py", "surrealdb_tpu/kvs/remote.py")
_DECISION_FN = re.compile(r"commit|prepare|decide|resolve|mark|split")

_SEAM_FILES = (
    "surrealdb_tpu/kvs/remote.py",
    "surrealdb_tpu/kvs/shard.py",
    "surrealdb_tpu/node.py",
)
_SEAM_FORBIDDEN = {
    ("time", "time"),
    ("time", "monotonic"),
    ("time", "sleep"),
    ("socket", "socket"),
    ("socket", "create_connection"),
}

_NOTIFY_FNS = {
    "surrealdb_tpu/kvs/ds.py": ("notify",),
    "surrealdb_tpu/exec/document.py": ("notify_lives",),
    "surrealdb_tpu/server/fanout.py": ("deliver",),
}
_NOTIFY_LOCK_OK = {"append", "pop", "popleft", "get", "clear",
                   "count_for", "add", "discard"}
_SEND_ATTRS = {"sendall", "send", "_ws_send", "sendto", "write"}

_KNN_FILE = "surrealdb_tpu/idx/shardvec.py"
_KNN_DEADLINE_FNS = ("scatter_gather", "merge_topk")
_KNN_LOCK_FNS = ("scatter_gather", "merge_topk", "_scatter_round",
                 "_sync_part", "refresh_parts")
_KNN_LOCK_OK = {"append", "pop", "get", "add", "discard", "span",
                "items", "values", "keys", "_repartition"}

_MEM_SCAN_PREFIXES = ("surrealdb_tpu/idx/", "surrealdb_tpu/device/")
# PR 14: the columnar executor's module state (column-store caches,
# counters) is covered too — its caches must register with the
# accountant (kvs/ds.py `col` account) or sit on the explicit allowlist
_MEM_SCAN_FILES = ("surrealdb_tpu/server/fanout.py",
                   "surrealdb_tpu/exec/batch.py",
                   "surrealdb_tpu/exec/vops.py",
                   "surrealdb_tpu/col.py")
_MEM_REGISTRATION_FNS = {
    "surrealdb_tpu/resource.py": ("register", "maybe_evict",
                                  "checkpoint", "throttle"),
    "surrealdb_tpu/idx/vector.py": ("_vec_mem_bytes", "_ann_mem_bytes",
                                    "_stats_mem_bytes",
                                    "_mem_evict_vec"),
    # PR 15: every sealed segment's graph is an ann-class account —
    # size/evict coverage plus the lifecycle entries that keep the
    # table consistent with the accountant (rename-proof)
    "surrealdb_tpu/idx/segments.py": ("_ann_bytes", "_evict_graph",
                                      "maybe_maintain", "reset"),
    "surrealdb_tpu/server/fanout.py": ("_mem_bytes", "_mem_evict"),
    "surrealdb_tpu/device/handlers.py": ("_admit", "_admit_share",
                                         "mem_used",
                                         "mem_used_device0"),
    # mesh execution layer: every per-device block table must expose
    # its install-time estimate + resident-bytes coverage, and the
    # budget-aware placement rule itself is rename-proofed
    "surrealdb_tpu/device/mesh.py": ("estimate_device_bytes",
                                     "device_nbytes", "pick_ndev"),
    "surrealdb_tpu/kvs/ds.py": ("_ft_cache_bytes", "_csr_mem_bytes",
                                "_csr_mem_evict", "_col_mem_bytes",
                                "_col_mem_evict"),
    "surrealdb_tpu/exec/batch.py": ("store_nbytes", "store_evict"),
}
_CONTAINER_CALLS = {"dict", "list", "set", "OrderedDict", "deque",
                    "defaultdict"}
_MEM_ALLOW = {
    ("surrealdb_tpu/idx/vector.py", "rids"),
    ("surrealdb_tpu/idx/vector.py", "row_index"),
    ("surrealdb_tpu/idx/vector.py", "_ann_dirty"),
    ("surrealdb_tpu/idx/shardvec.py", "parts"),
    ("surrealdb_tpu/device/handlers.py", "vec"),
    ("surrealdb_tpu/device/handlers.py", "csr"),
    ("surrealdb_tpu/device/handlers.py", "ann"),
    ("surrealdb_tpu/device/handlers.py", "ft"),
    ("surrealdb_tpu/device/handlers.py", "_staging"),
    ("surrealdb_tpu/device/handlers.py", "_ann_staging"),
    ("surrealdb_tpu/device/handlers.py", "_reserved"),
    ("surrealdb_tpu/server/fanout.py", "q"),
    ("surrealdb_tpu/server/fanout.py", "_queues"),
    ("surrealdb_tpu/device/annstore.py", "_jit_cache"),
    ("surrealdb_tpu/device/csrstore.py", "_jit_cache"),
    ("surrealdb_tpu/device/kernelstats.py", "COUNTS"),
    ("surrealdb_tpu/device/kernelstats.py", "_SEEN"),
    ("surrealdb_tpu/device/kernelstats.py", "MESH_LAST"),
    ("surrealdb_tpu/device/supervisor.py", "compile_counts"),
    ("surrealdb_tpu/device/supervisor.py", "counters"),
    ("surrealdb_tpu/device/supervisor.py", "_pending"),
    ("surrealdb_tpu/device/supervisor.py", "_loaded"),
    ("surrealdb_tpu/device/supervisor.py", "_oom_keys"),
    ("surrealdb_tpu/device/batcher.py", "queue"),
    ("surrealdb_tpu/server/fanout.py", "_warned"),
    ("surrealdb_tpu/server/fanout.py", "_subs"),
    ("surrealdb_tpu/server/fanout.py", "_by_table"),
    ("surrealdb_tpu/server/fanout.py", "lids"),
    ("surrealdb_tpu/server/fanout.py", "_routes"),
    ("surrealdb_tpu/server/fanout.py", "_sessions"),
    ("surrealdb_tpu/server/fanout.py", "_wconds"),
    ("surrealdb_tpu/idx/fulltext.py", "_STOP_SUFFIXES"),
    ("surrealdb_tpu/device/annstore.py", "cfg"),
    ("surrealdb_tpu/device/vecstore.py", "cfg"),
    # batch-lifetime column cache: dies with its BatchCols (one
    # streaming batch); the persistent store is the accountant-covered
    # `col` account on kvs/ds.py
    ("surrealdb_tpu/exec/batch.py", "_cols"),
}

_FOLLOWER_FILE = "surrealdb_tpu/kvs/remote.py"
_FOLLOWER_FNS = ("follower_read_proof", "_follower_read_allowed",
                 "_dispatch")
_FOLLOWER_OPS_OK = {"get", "range"}

_JAX_ALLOWED = (
    "surrealdb_tpu/device/",
    "surrealdb_tpu/parallel/",
    "surrealdb_tpu/ops/",
    "surrealdb_tpu/ml/onnx.py",
)

_NOTIFY_BUILTIN_OK = {"len", "list", "bytes", "isinstance", "getattr",
                      "str", "dict", "set", "sorted"}


def _imports_jax(node) -> bool:
    if isinstance(node, ast.Import):
        return any(a.name == "jax" or a.name.startswith("jax.")
                   for a in node.names)
    if isinstance(node, ast.ImportFrom):
        m = node.module or ""
        return m == "jax" or m.startswith("jax.")
    return False


def _is_thread_call(node: ast.Call) -> bool:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id == "Thread"
    if isinstance(f, ast.Attribute):
        return f.attr == "Thread"
    return False


def _calls_attr(tree, attr: str) -> bool:
    for n in ast.walk(tree):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr == attr:
            return True
    return False


def _is_lock_ctx(item: ast.withitem) -> bool:
    e = item.context_expr
    if isinstance(e, ast.Attribute):
        return "lock" in e.attr or "cond" in e.attr
    if isinstance(e, ast.Call) and isinstance(e.func, ast.Attribute):
        return "lock" in e.func.attr
    return False


def _check_notify_fns(fi: FileInfo, fn_names) -> list[Finding]:
    rel, tree = fi.rel, fi.tree
    found = set()
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef) \
                or node.name not in fn_names:
            continue
        found.add(node.name)
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in _SEND_ATTRS \
                    and not fi.waived(sub.lineno, "notify"):
                findings.append(Finding(
                    "notify", rel, sub.lineno,
                    f"`{sub.func.attr}(` inside "
                    f"{node.name} — socket I/O is never allowed on the "
                    f"notify/capture path (route through a session "
                    f"outbox writer)",
                    func=node.name,
                    detail=f"send:{sub.func.attr}"))
            if not isinstance(sub, ast.With):
                continue
            if not any(_is_lock_ctx(it) for it in sub.items):
                continue
            for inner in ast.walk(sub):
                if inner is sub or not isinstance(inner, ast.Call):
                    continue
                f = inner.func
                ok = (
                    (isinstance(f, ast.Attribute)
                     and f.attr in _NOTIFY_LOCK_OK)
                    or (isinstance(f, ast.Name)
                        and f.id in _NOTIFY_BUILTIN_OK)
                )
                if not ok and not fi.waived(inner.lineno, "notify"):
                    label = (f.attr if isinstance(f, ast.Attribute)
                             else getattr(f, "id", "<call>"))
                    findings.append(Finding(
                        "notify", rel, inner.lineno,
                        f"call `{label}(` under "
                        f"a lock inside {node.name} — handler "
                        f"invocation / blocking work while holding the "
                        f"datastore lock stalls every writer (rule 7)",
                        func=node.name, detail=f"lock:{label}"))
    for name in fn_names:
        if name not in found:
            findings.append(Finding(
                "notify", rel, 1,
                f"rule-7 function `{name}` not found — the "
                f"fan-out delivery contract is no longer being checked "
                f"(update _NOTIFY_FNS after a rename)",
                func=name, detail=f"missing:{name}"))
    return findings


def _check_knn_fns(fi: FileInfo) -> list[Finding]:
    rel, tree = fi.rel, fi.tree
    wanted = set(_KNN_DEADLINE_FNS) | set(_KNN_LOCK_FNS)
    found = set()
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef) \
                or node.name not in wanted:
            continue
        found.add(node.name)
        if node.name in _KNN_DEADLINE_FNS \
                and not _calls_attr(node, "check_deadline") \
                and not fi.waived(node.lineno, "knn"):
            findings.append(Finding(
                "knn", rel, node.lineno,
                f"{node.name} never calls "
                f"check_deadline() — a KILL/timeout must be able to "
                f"land between per-shard dispatches (rule 8)",
                func=node.name, detail=f"deadline:{node.name}"))
        for sub in ast.walk(node):
            if not isinstance(sub, ast.With):
                continue
            if not any(_is_lock_ctx(it) for it in sub.items):
                continue
            for inner in ast.walk(sub):
                if inner is sub or not isinstance(inner, ast.Call):
                    continue
                f = inner.func
                ok = (
                    (isinstance(f, ast.Attribute)
                     and f.attr in _KNN_LOCK_OK)
                    or (isinstance(f, ast.Name)
                        and f.id in _NOTIFY_BUILTIN_OK)
                )
                if not ok and not fi.waived(inner.lineno, "knn"):
                    label = (f.attr if isinstance(f, ast.Attribute)
                             else getattr(f, "id", "<call>"))
                    findings.append(Finding(
                        "knn", rel, inner.lineno,
                        f"call `{label}(` under "
                        f"a lock inside {node.name} — a shard-map "
                        f"lock held across a remote dispatch "
                        f"serializes every query on the node (rule 8)",
                        func=node.name, detail=f"lock:{label}"))
    for name in sorted(wanted - found):
        findings.append(Finding(
            "knn", rel, 1,
            f"rule-8 function `{name}` not found — the "
            f"scatter-gather KNN contract is no longer being checked "
            f"(update the rule-8 tables after a rename)",
            func=name, detail=f"missing:{name}"))
    return findings


def _check_follower_fns(fi: FileInfo) -> list[Finding]:
    rel, tree = fi.rel, fi.tree
    findings = []
    fns = {n.name: n for n in ast.walk(tree)
           if isinstance(n, ast.FunctionDef)}
    for name in _FOLLOWER_FNS:
        if name not in fns:
            findings.append(Finding(
                "follower", rel, 1,
                f"rule-10 function `{name}` not found — the "
                f"follower-read proof contract is no longer being "
                f"checked (update the rule-10 table after a rename)",
                func=name, detail=f"missing:{name}"))
    gate = fns.get("_follower_read_allowed")
    if gate is not None:
        for sub in ast.walk(gate):
            if not isinstance(sub, ast.Compare):
                continue
            for n2 in ast.walk(sub):
                if isinstance(n2, ast.Constant) \
                        and isinstance(n2.value, str) \
                        and n2.value not in _FOLLOWER_OPS_OK \
                        and not fi.waived(n2.lineno, "follower"):
                    findings.append(Finding(
                        "follower", rel, n2.lineno,
                        f"op {n2.value!r} admitted "
                        f"to the follower-served read path — only "
                        f"get/range may serve against a proof-pinned "
                        f"snapshot (rule 10: a follower-served `snap`/"
                        f"`get_latest` is the stale-forever hole PR 5 "
                        f"closed)",
                        func="_follower_read_allowed",
                        detail=f"op:{n2.value}"))
        if not any(isinstance(n2, ast.Attribute) and n2.attr == "fsnaps"
                   for n2 in ast.walk(gate)):
            findings.append(Finding(
                "follower", rel, gate.lineno,
                f"_follower_read_allowed no "
                f"longer checks the proof-registered snapshot set "
                f"(fsnaps) — a replica would serve reads against "
                f"snapshots that never passed the closed-timestamp "
                f"proof (rule 10)",
                func="_follower_read_allowed", detail="fsnaps"))
    disp = fns.get("_dispatch")
    if disp is not None:
        for req in ("_follower_read_allowed", "follower_read_proof"):
            if not _calls_attr(disp, req):
                findings.append(Finding(
                    "follower", rel, disp.lineno,
                    f"_dispatch never calls "
                    f"`{req}()` — replica-side reads are being served "
                    f"outside the closed-timestamp proof (rule 10)",
                    func="_dispatch", detail=f"calls:{req}"))
    return findings


def _is_container_value(v) -> bool:
    if isinstance(v, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                      ast.ListComp, ast.SetComp)):
        return True
    if isinstance(v, ast.Call):
        f = v.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None
        )
        return name in _CONTAINER_CALLS
    return False


def _check_mem_accounting(fi: FileInfo) -> list[Finding]:
    rel, tree = fi.rel, fi.tree
    findings = []

    def flag(name, lineno):
        if name.startswith("__") and name.endswith("__"):
            return
        if (rel, name) in _MEM_ALLOW or fi.waived(lineno, "mem-account"):
            return
        findings.append(Finding(
            "mem-account", rel, lineno,
            f"container `{name}` in {rel} is "
            f"neither registered with the memory accountant "
            f"(resource.register size/evict coverage) nor on the "
            f"rule-9 allowlist — unaccounted derived state is how the "
            f"node OOMs instead of degrading",
            detail=f"container:{name}"))

    for node in ast.iter_child_nodes(tree):
        if isinstance(node, ast.Assign) and _is_container_value(
                node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    flag(t.id, node.lineno)
        elif isinstance(node, ast.AnnAssign) \
                and node.value is not None \
                and _is_container_value(node.value) \
                and isinstance(node.target, ast.Name):
            flag(node.target.id, node.lineno)
        if not isinstance(node, ast.ClassDef):
            continue
        for fn in node.body:
            if not (isinstance(fn, ast.FunctionDef)
                    and fn.name == "__init__"):
                continue
            for sub in ast.walk(fn):
                tgt = val = None
                if isinstance(sub, ast.Assign):
                    val = sub.value
                    tgt = sub.targets[0] if len(sub.targets) == 1 \
                        else None
                elif isinstance(sub, ast.AnnAssign):
                    val, tgt = sub.value, sub.target
                if val is None or not _is_container_value(val):
                    continue
                if isinstance(tgt, ast.Attribute) \
                        and isinstance(tgt.value, ast.Name) \
                        and tgt.value.id == "self":
                    flag(tgt.attr, sub.lineno)
    return findings


def _check_mem_registration_fns(fi: FileInfo) -> list[Finding]:
    wanted = _MEM_REGISTRATION_FNS.get(fi.rel)
    if not wanted:
        return []
    have = {n.name for n in ast.walk(fi.tree)
            if isinstance(n, ast.FunctionDef)}
    return [
        Finding(
            "mem-account", fi.rel, 1,
            f"rule-9 registration function `{name}` not found — "
            f"memory-accounting coverage is no longer wired (update "
            f"the rule-9 tables after a rename)",
            func=name, detail=f"missing:{name}")
        for name in wanted if name not in have
    ]


def check_fileinfo(fi: FileInfo) -> list[Finding]:
    """All per-file legacy rules against one pre-parsed file."""
    rel, tree = fi.rel, fi.tree
    findings: list[Finding] = []
    jax_ok = any(
        rel.startswith(p) or rel == p.rstrip("/")
        for p in _JAX_ALLOWED
    )
    for node in ast.walk(tree):
        if not jax_ok and _imports_jax(node) \
                and not fi.waived(node.lineno, "jax-import"):
            findings.append(Finding(
                "jax-import", rel, node.lineno,
                f"`import jax` outside "
                f"{'|'.join(_JAX_ALLOWED)} — backend init must never "
                f"run on a query worker thread (dispatch via "
                f"surrealdb_tpu.device instead)",
                detail="import-jax"))
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            if not fi.waived(node.lineno, "bare-except"):
                findings.append(Finding(
                    "bare-except", rel, node.lineno,
                    "bare `except:` swallows "
                    "cancellation — name the exception types",
                    detail=f"bare-except@{node.lineno}"))
        if isinstance(node, ast.Call) and _is_thread_call(node):
            daemon = next(
                (kw for kw in node.keywords if kw.arg == "daemon"), None
            )
            is_daemon = (
                daemon is not None
                and isinstance(daemon.value, ast.Constant)
                and daemon.value.value is True
            )
            if not is_daemon and not fi.waived(node.lineno,
                                               "thread-daemon"):
                findings.append(Finding(
                    "thread-daemon", rel, node.lineno,
                    "non-daemon Thread() without "
                    "`daemon=True` or a `# robust: joined` pragma — "
                    "blocks SIGTERM drain",
                    detail=f"thread@{node.lineno}"))
    if rel in _SEAM_FILES:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)):
                continue
            if (f.value.id, f.attr) in _SEAM_FORBIDDEN \
                    and not fi.waived(node.lineno, "seam"):
                findings.append(Finding(
                    "seam", rel, node.lineno,
                    f"raw `{f.value.id}.{f.attr}()`"
                    f" outside the kvs/net.py seam — route it through "
                    f"Clock/Runtime/Transport or the deterministic "
                    f"simulator cannot virtualize it",
                    detail=f"{f.value.id}.{f.attr}"))
    if rel in _TWOPC_FILES:
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not _DECISION_FN.search(fn.name):
                continue
            for node in ast.walk(fn):
                if (isinstance(node, ast.ExceptHandler)
                        and len(node.body) == 1
                        and isinstance(node.body[0], ast.Pass)
                        and not fi.waived(node.lineno, "twopc-swallow")):
                    findings.append(Finding(
                        "twopc-swallow", rel, node.lineno,
                        f"silent `except: pass` in "
                        f"2PC decision path {fn.name} — count it, "
                        f"re-raise, or add a `# robust:` pragma",
                        func=fn.name, detail=f"swallow:{fn.name}"))
    if rel in _NOTIFY_FNS:
        findings.extend(_check_notify_fns(fi, _NOTIFY_FNS[rel]))
    if rel == _KNN_FILE:
        findings.extend(_check_knn_fns(fi))
    if rel == _FOLLOWER_FILE:
        findings.extend(_check_follower_fns(fi))
    if any(rel.startswith(p) for p in _MEM_SCAN_PREFIXES) \
            or rel in _MEM_SCAN_FILES:
        findings.extend(_check_mem_accounting(fi))
    findings.extend(_check_mem_registration_fns(fi))
    if rel.endswith("exec/stream.py"):
        for node in ast.iter_child_nodes(tree):
            if not (isinstance(node, ast.ClassDef)
                    and node.name.endswith("Op")):
                continue
            ex = next(
                (n for n in node.body
                 if isinstance(n, ast.FunctionDef)
                 and n.name == "_execute"),
                None,
            )
            if ex is None:
                continue
            has_loop = any(
                isinstance(n, (ast.For, ast.While)) for n in ast.walk(ex)
            )
            if not has_loop:
                continue
            ok = _calls_attr(ex, "check_deadline") or _calls_attr(
                ex, "execute"
            )
            if not ok and not fi.waived(node.lineno, "stream-deadline"):
                findings.append(Finding(
                    "stream-deadline", rel, node.lineno,
                    f"streaming operator "
                    f"{node.name}._execute loops without "
                    f"ctx.check_deadline() or a child .execute(ctx) — "
                    f"unbounded under KILL/timeout",
                    func=f"{node.name}._execute",
                    detail=f"op:{node.name}"))
    return findings


def check_file(path: str, rel: str) -> list[Finding]:
    """Parse one file standalone and run the legacy rules (the
    check_robustness.py `check_file` compatibility surface)."""
    with open(path, encoding="utf-8") as f:
        src = f.read()
    rel = rel.replace(os.sep, "/")
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding("parse", rel, e.lineno or 1,
                        f"syntax error: {e.msg}", detail="syntax")]
    return check_fileinfo(FileInfo(path, rel, src, tree))
