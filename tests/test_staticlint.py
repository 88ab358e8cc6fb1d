"""tools/staticlint — framework, analyses, baseline, and mutation tests.

Three layers:

1. fixture trees (tests/staticlint_fixtures/): each finding class has a
   minimal package that must trigger it — the PR-9 deadlock shape
   (ds.lock held across a remote read), a lock-order cycle, a
   deadline-free streaming loop, a stale baseline entry, reasonless
   pragmas;
2. mutation tests: copy the REAL tree, re-introduce each hazard class,
   and prove the conformance gate goes red (and that deleting a
   baselined function trips the fail-closed baseline);
3. the tier-1 wrapper: the full pass over the repo is clean, parses
   each file exactly once, and finishes far inside the 30 s budget.
"""

from __future__ import annotations

import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "staticlint_fixtures")

sys.path.insert(0, os.path.join(ROOT, "tools"))

import staticlint  # noqa: E402
from staticlint.baseline import parse_toml_subset  # noqa: E402


def _rules(rep):
    return {f.rule for f in rep.findings}


def _run_fixture(name):
    return staticlint.run(os.path.join(FIXTURES, name))


# -- fixture trees: every finding class fires -------------------------------

def test_pr9_deadlock_shape_is_caught():
    """The exact PR-9 bug: ds.lock held across a remote vn read."""
    rep = _run_fixture("pr9_deadlock")
    hits = [f for f in rep.findings if f.rule == "lock-held"]
    assert hits, [f.text() for f in rep.findings]
    f = hits[0]
    assert "idx/vecidx.py" in f.rel
    assert f.func == "TpuVectorIndex.vector_index_update"
    assert "RemoteTx.get" in f.message
    assert "self.ds.lock" in f.message
    # the witness explains WHY it blocks (reaches a socket primitive)
    assert "recv" in f.message or "send" in f.message


def test_lock_order_cycle_is_caught_with_witness():
    rep = _run_fixture("lock_cycle")
    hits = [f for f in rep.findings if f.rule == "lock-order"]
    assert hits, [f.text() for f in rep.findings]
    msg = hits[0].message
    assert "A.lock" in msg and "B.lock" in msg
    # both directions are witnessed, one of them interprocedural
    assert "rev" in msg and ("fwd" in msg or "_grab_b" in msg)


def test_deadline_free_streaming_loop_is_caught():
    rep = _run_fixture("deadline_loop")
    assert "deadline" in _rules(rep), [f.text() for f in rep.findings]
    # the legacy operator rule fires on the same shape
    assert "stream-deadline" in _rules(rep)


def test_stale_and_reasonless_baseline_entries_are_findings():
    rep = _run_fixture("stale_baseline")
    details = {f.detail for f in rep.findings if f.rule == "baseline"}
    assert any(d.startswith("stale:") for d in details), details
    assert any(d.startswith("noreason:") for d in details), details


def test_reasonless_and_malformed_pragmas_fail_the_gate():
    rep = _run_fixture("bare_pragma")
    details = {f.detail for f in rep.findings if f.rule == "pragma"}
    assert any(d.startswith("bare-robust") for d in details), details
    assert any(d.startswith("noreason-lint") for d in details), details
    assert any(d.startswith("malformed-lint") for d in details), details


def test_existing_repo_pragmas_all_carry_reasons():
    rep = staticlint.run(ROOT)
    assert not [f for f in rep.findings if f.rule == "pragma"]


# -- framework mechanics ----------------------------------------------------

def test_single_parse_per_file():
    rep = staticlint.run(ROOT)
    assert rep.parse_count == rep.files > 50


def test_json_report_shape():
    rep = staticlint.run(os.path.join(FIXTURES, "pr9_deadlock"))
    j = rep.to_json()
    assert set(j) >= {"ok", "findings", "timings_s", "total_s",
                      "files", "parse_count", "baselined"}
    assert j["findings"], j
    f0 = j["findings"][0]
    assert set(f0) == {"rule", "file", "line", "func", "detail",
                       "message"}
    # per-rule wall time is reported for every analysis stage
    assert {"lock-order", "lock-held", "deadline",
            "legacy-rules"} <= set(j["timings_s"])


def test_toml_subset_parser_roundtrip():
    text = (
        "# comment\n"
        "[[suppress]]\n"
        'rule = "lock-held"\n'
        "func = 'A.b'\n"
        'reason = "why (with \\"quotes\\")"\n'
        "\n"
        "[[suppress]]\n"
        'rule = "deadline"\n'
        'reason = "x"  # trailing comment\n'
    )
    tables = parse_toml_subset(text)
    assert len(tables) == 2
    assert tables[0][0]["rule"] == "lock-held"
    assert tables[0][0]["func"] == "A.b"
    assert 'quotes' in tables[0][0]["reason"]
    assert tables[1][0]["reason"] == "x"
    with pytest.raises(ValueError):
        parse_toml_subset("[[other]]\n")
    with pytest.raises(ValueError):
        parse_toml_subset('rule = "x"\n')


def test_lint_pragma_waives_own_and_next_line(tmp_path):
    tree = tmp_path / "surrealdb_tpu"
    tree.mkdir()
    (tree / "__init__.py").write_text("")
    (tree / "exec").mkdir()
    (tree / "exec" / "__init__.py").write_text("")
    (tree / "exec" / "stream.py").write_text(
        "# lint: stream-deadline(fixture: loop is bounded by caller)\n"
        "class WaivedOp:\n"
        "    def _execute(self, ctx):\n"
        "        # lint: deadline(fixture: loop is bounded by caller)\n"
        "        while self.more():\n"
        "            pass\n"
    )
    rep = staticlint.run(str(tmp_path))
    assert "stream-deadline" not in _rules(rep), \
        [f.text() for f in rep.findings]
    assert "deadline" not in _rules(rep), \
        [f.text() for f in rep.findings]


# -- compatibility shim -----------------------------------------------------

def _load_shim():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_robustness",
        os.path.join(ROOT, "tools", "check_robustness.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_shim_scan_clean_and_main_green():
    mod = _load_shim()
    assert mod.scan(ROOT) == []
    assert mod.main([ROOT]) == 0


def test_shim_check_file_keeps_legacy_messages(tmp_path):
    mod = _load_shim()
    bad = tmp_path / "ds.py"
    bad.write_text(
        "class Datastore:\n"
        "    def notify(self, n):\n"
        "        with self.lock:\n"
        "            for h in self.handlers:\n"
        "                h(n)\n"
        "            self.sock.sendall(b'x')\n"
    )
    findings = mod.check_file(str(bad), "surrealdb_tpu/kvs/ds.py")
    assert any("sendall" in f for f in findings)
    assert any("under a lock" in f for f in findings)


# -- mutation tests: every analysis still bites on the real tree ------------

@pytest.fixture(scope="module")
def tree_copy_base(tmp_path_factory):
    base = tmp_path_factory.mktemp("mutated")
    src = base / "pristine"
    shutil.copytree(
        os.path.join(ROOT, "surrealdb_tpu"), src / "surrealdb_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    (src / "tools" / "staticlint").mkdir(parents=True)
    shutil.copy(
        os.path.join(ROOT, "tools", "staticlint", "baseline.toml"),
        src / "tools" / "staticlint" / "baseline.toml")
    rep = staticlint.run(str(src))
    assert rep.findings == [], [f.text() for f in rep.findings]
    return src


def _mutate(base, name: str, rel: str, old: str, new: str,
            append: str | None = None):
    root = base.parent / name
    shutil.copytree(base, root)
    p = root / rel
    src = p.read_text()
    if old:
        assert old in src, f"mutation anchor gone: {old[:60]!r}"
        src = src.replace(old, new, 1)
    if append:
        src += append
    p.write_text(src)
    return str(root)


def test_mutation_lock_cycle_turns_gate_red(tree_copy_base):
    root = _mutate(
        tree_copy_base, "m_cycle", "surrealdb_tpu/buc.py", "", "",
        append=(
            "\n\nclass _LintProbeA:\n"
            "    def __init__(self):\n"
            "        import threading\n"
            "        self.lock = threading.Lock()\n"
            "\n\nclass _LintProbeB:\n"
            "    def __init__(self):\n"
            "        import threading\n"
            "        self.lock = threading.Lock()\n"
            "\n\nclass _LintProbePair:\n"
            "    def __init__(self):\n"
            "        self.a = _LintProbeA()\n"
            "        self.b = _LintProbeB()\n"
            "    def fwd(self):\n"
            "        with self.a.lock:\n"
            "            with self.b.lock:\n"
            "                pass\n"
            "    def rev(self):\n"
            "        with self.b.lock:\n"
            "            with self.a.lock:\n"
            "                pass\n"
        ))
    rep = staticlint.run(root)
    assert "lock-order" in _rules(rep), [f.text() for f in rep.findings]


def test_mutation_blocking_under_lock_turns_gate_red(tree_copy_base):
    root = _mutate(
        tree_copy_base, "m_block", "surrealdb_tpu/idx/vector.py",
        "        with self.lock, self.rw.write():\n"
        "            with self._pin_lock:\n",
        "        with self.lock, self.rw.write():\n"
        "            _time.sleep(0.01)\n"
        "            with self._pin_lock:\n",
    )
    rep = staticlint.run(root)
    hits = [f for f in rep.findings if f.rule == "lock-held"]
    assert any("sleep" in f.message for f in hits), \
        [f.text() for f in rep.findings]


def test_mutation_deadline_free_loop_turns_gate_red(tree_copy_base):
    root = _mutate(
        tree_copy_base, "m_deadline", "surrealdb_tpu/exec/stream.py",
        "", "",
        append=(
            "\n\nclass _LintProbeOp(Operator):\n"
            "    def _execute(self, ctx):\n"
            "        out = []\n"
            "        while True:\n"
            "            row = self.child.pull()\n"
            "            if row is None:\n"
            "                return out\n"
            "            out.append(row)\n"
        ))
    rep = staticlint.run(root)
    rules = _rules(rep)
    assert "stream-deadline" in rules or "deadline" in rules, \
        [f.text() for f in rep.findings]


def test_mutation_deleting_baselined_function_turns_gate_red(
        tree_copy_base):
    """Fail-closed baseline: renaming KvEngine.log_commit (covered by
    baseline entries) leaves stale entries AND un-baselined findings —
    the gate must go red, not silently absorb the rename."""
    root = _mutate(
        tree_copy_base, "m_stale", "surrealdb_tpu/kvs/remote.py",
        "    def log_commit(self, writes: dict):",
        "    def log_commit_renamed(self, writes: dict):",
    )
    rep = staticlint.run(root)
    assert any(f.rule == "baseline" and "stale" in f.detail
               for f in rep.findings), [f.text() for f in rep.findings]


def test_mutation_bare_pragma_turns_gate_red(tree_copy_base):
    root = _mutate(
        tree_copy_base, "m_pragma", "surrealdb_tpu/buc.py", "", "",
        append="\n# robust:\n")
    rep = staticlint.run(root)
    assert "pragma" in _rules(rep)


# -- ported legacy rules still bite (mutation per family) -------------------

LEGACY_MUTATIONS = [
    ("bare-except", "surrealdb_tpu/buc.py", None,
     "\n\ndef _probe():\n    try:\n        return 1\n"
     "    except:\n        return 2\n"),
    ("thread-daemon", "surrealdb_tpu/buc.py", None,
     "\n\ndef _probe():\n    import threading\n"
     "    threading.Thread(target=print).start()\n"),
    ("jax-import", "surrealdb_tpu/buc.py", None,
     "\n\nimport jax\n"),
    ("seam", "surrealdb_tpu/node.py", None,
     "\n\ndef _probe():\n    import time\n    return time.time()\n"),
    ("twopc-swallow", "surrealdb_tpu/kvs/shard.py", None,
     "\n\ndef _probe_commit():\n    try:\n        return 1\n"
     "    except ValueError:\n        pass\n"),
]


@pytest.mark.parametrize(
    "rule,rel,old,append",
    LEGACY_MUTATIONS, ids=[m[0] for m in LEGACY_MUTATIONS])
def test_mutation_legacy_rules_bite(tree_copy_base, rule, rel, old,
                                    append):
    root = _mutate(tree_copy_base, f"m_{rule}", rel, old or "", "",
                   append=append)
    rep = staticlint.run(root)
    assert rule in _rules(rep), [f.text() for f in rep.findings]


def test_mutation_rename_proof_contract_fns(tree_copy_base):
    """Renaming a rule-8 policed function is itself a finding."""
    root = _mutate(
        tree_copy_base, "m_rename", "surrealdb_tpu/idx/shardvec.py",
        "def merge_topk(", "def merge_topk_renamed(")
    rep = staticlint.run(root)
    assert any(f.rule == "knn" and "not found" in f.message
               for f in rep.findings), [f.text() for f in rep.findings]


# -- tier-1 wrapper: the repo itself ---------------------------------------

def test_full_tree_clean_and_fast():
    rep = staticlint.run(ROOT)
    assert rep.findings == [], "\n".join(
        f"[{f.rule}] {f.text()}" for f in rep.findings)
    assert rep.baselined > 0          # the triage ledger is live
    assert rep.parse_count == rep.files
    assert rep.total_s < 30.0, f"staticlint took {rep.total_s:.1f}s"


def test_mutation_renaming_blocking_seed_turns_gate_red(tree_copy_base):
    """The blocking-seed table has the same rename-proof teeth as the
    legacy contract rules: losing RetryPolicy.run silently un-blocks
    its whole caller cone, so it must be a finding."""
    root = _mutate(
        tree_copy_base, "m_seed", "surrealdb_tpu/kvs/remote.py",
        "    def run(self, fn", "    def run_renamed(self, fn")
    rep = staticlint.run(root)
    assert any(f.rule == "lock-held" and "missing-seed" in f.detail
               for f in rep.findings), [f.text() for f in rep.findings]


# -- review regressions -----------------------------------------------------

def _tiny_tree(tmp_path, body: str):
    tree = tmp_path / "surrealdb_tpu"
    tree.mkdir()
    (tree / "__init__.py").write_text("")
    (tree / "probe.py").write_text(body)
    return str(tmp_path)


def test_self_deadlock_on_plain_lock_is_caught(tmp_path):
    """with self.lock: self._inner() where _inner retakes the same
    non-reentrant Lock — instant deadlock, must be a lock-order
    finding (intraprocedural and through a call)."""
    root = _tiny_tree(tmp_path, (
        "import threading\n\n\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self.lock = threading.Lock()\n\n"
        "    def _inner(self):\n"
        "        with self.lock:\n"
        "            return 1\n\n"
        "    def outer_call(self):\n"
        "        with self.lock:\n"
        "            return self._inner()\n\n"
        "    def outer_inline(self):\n"
        "        with self.lock:\n"
        "            with self.lock:\n"
        "                return 2\n"
    ))
    rep = staticlint.run(root)
    hits = [f for f in rep.findings
            if f.rule == "lock-order" and "self:" in f.detail]
    assert len(hits) == 2, [f.text() for f in rep.findings]
    assert {f.func for f in hits} == {"Box.outer_call",
                                      "Box.outer_inline"}
    # an RLock re-acquisition must stay quiet
    (tmp_path / "r2").mkdir()
    root2 = _tiny_tree(tmp_path / "r2", (
        "import threading\n\n\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self.lock = threading.RLock()\n\n"
        "    def outer(self):\n"
        "        with self.lock:\n"
        "            with self.lock:\n"
        "                return 2\n"
    ))
    rep2 = staticlint.run(root2)
    assert not [f for f in rep2.findings if f.rule == "lock-order"], \
        [f.text() for f in rep2.findings]


def test_generator_send_under_lock_is_not_flagged(tmp_path):
    root = _tiny_tree(tmp_path, (
        "import threading\n\n\n"
        "class Pump:\n"
        "    def __init__(self, gen, sock):\n"
        "        self.lock = threading.Lock()\n"
        "        self.gen = gen\n"
        "        self.sock = sock\n\n"
        "    def step(self, v):\n"
        "        with self.lock:\n"
        "            return self.gen.send(v)\n\n"
        "    def push(self, v):\n"
        "        with self.lock:\n"
        "            return self.sock.send(v)\n"
    ))
    rep = staticlint.run(root)
    hits = [f for f in rep.findings if f.rule == "lock-held"]
    assert len(hits) == 1, [f.text() for f in rep.findings]
    assert hits[0].func == "Pump.push"


def test_closure_loop_reports_once_under_the_closure(tmp_path):
    tree = tmp_path / "surrealdb_tpu"
    (tree / "idx").mkdir(parents=True)
    (tree / "__init__.py").write_text("")
    (tree / "idx" / "__init__.py").write_text("")
    (tree / "idx" / "shardvec.py").write_text(
        "def scatter_gather(parts, sock):\n"
        "    def drain():\n"
        "        while True:\n"
        "            sock.recv(1)\n"
        "    return drain\n"
    )
    rep = staticlint.run(str(tmp_path))
    hits = [f for f in rep.findings if f.rule == "deadline"]
    assert len(hits) == 1, [f.text() for f in rep.findings]
    assert hits[0].func == "scatter_gather.drain"
