"""tpulint: every rule proven by a firing fixture AND a clean minimal
pair, suppression comments, JSON output, and the tier-1 repo gate.

The fixture tests go through the public API (``lint_files`` with
``unscoped=True`` — fixtures live in tmp dirs outside each rule's
file-scope globs); the repo gate shells ``python -m tools.tpulint``
exactly the way CI does.  That one subprocess run covers the metric
(TPL501) and manifest (TPL601) checkers under the unified entrypoint —
absorbing the old per-CLI shell-outs of ``tools/lint_metrics.py`` and
``tools/lint_manifests.py``, whose in-process ``lint()`` coverage stays
in test_obs.py / test_manifests.py via the shims.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.tpulint import all_rules, lint_files, lint_repo  # noqa: E402
from tools.tpulint.__main__ import main as tpulint_main  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore")


def _lint(tmp_path, source: str, select=None, name="snippet.py"):
    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    return lint_files([str(f)], root=tmp_path, select=select, unscoped=True)


def _codes(findings):
    return sorted({f.code for f in findings})


# --------------------------------------------------- TPL101 host-sync-in-loop
def test_tpl101_fires_on_sync_in_loop(tmp_path):
    found = _lint(tmp_path, """
        import numpy as np

        def drain(chain):
            out = []
            while chain:
                out.append(np.asarray(chain.pop(0)))
            return out
    """, select=["TPL101"])
    assert _codes(found) == ["TPL101"]
    assert "np.asarray" in found[0].message


def test_tpl101_quiet_on_sync_outside_loop(tmp_path):
    assert _lint(tmp_path, """
        import numpy as np

        def drain(chain):
            blocks = dispatch_all(chain)
            return np.asarray(blocks)
    """, select=["TPL101"]) == []


def test_tpl101_item_and_scalar_pull_fire(tmp_path):
    found = _lint(tmp_path, """
        def consume(devs):
            total = 0
            for d in devs:
                total += int(d[0])
                d.block_until_ready()
            return total
    """, select=["TPL101"])
    msgs = "\n".join(f.message for f in found)
    assert "int(<subscript>)" in msgs and "block_until_ready" in msgs


def test_tpl101_host_array_scalar_pull_is_free(tmp_path):
    # int()/float() off arrays the function itself built with np.* are
    # host-resident — no sync, no finding
    assert _lint(tmp_path, """
        import numpy as np

        def consume(block):
            lens = np.zeros(8)
            out = []
            for i in range(8):
                out.append(int(lens[i]))
            return out
    """, select=["TPL101"]) == []


# -------------------------------------------------- TPL102 jit-static-scalar
def test_tpl102_fires_on_bare_jit_with_scalar_param(tmp_path):
    found = _lint(tmp_path, """
        import jax

        @jax.jit
        def decode(tokens, chunk):
            return tokens[:chunk]
    """, select=["TPL102"])
    assert _codes(found) == ["TPL102"]
    assert "chunk" in found[0].message


def test_tpl102_quiet_with_static_argnums(tmp_path):
    assert _lint(tmp_path, """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(1,))
        def decode(tokens, chunk):
            return tokens[:chunk]

        @jax.jit
        def add(a, x):
            return a + x
    """, select=["TPL102"]) == []


# ---------------------------------------------- TPL201 guarded-field-access
def test_tpl201_fires_on_unlocked_access(tmp_path):
    found = _lint(tmp_path, """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self.depth = 0  # guarded-by: _lock

            def bump(self):
                self.depth += 1

            def read(self):
                return self.depth
    """, select=["TPL201"])
    assert len(found) == 2 and _codes(found) == ["TPL201"]


def test_tpl201_quiet_under_lock_and_writes_only_reads(tmp_path):
    assert _lint(tmp_path, """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self.depth = 0  # guarded-by: _lock
                self.total = 0  # guarded-by: _lock (writes)

            def bump(self):
                with self._lock:
                    self.depth += 1
                    self.total += 1

            def peek(self):
                return self.total  # racy read allowed by (writes)
    """, select=["TPL201"]) == []


def test_tpl201_catches_container_mutation(tmp_path):
    found = _lint(tmp_path, """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self.free = []  # guarded-by: _lock (writes)
                self.ref = {}  # guarded-by: _lock (writes)

            def leak(self, x):
                self.free.append(x)
                self.ref[x] = 1
    """, select=["TPL201"])
    assert len(found) == 2


# ------------------------------------------ TPL203 sanitizer-registry-drift
def test_tpl203_repo_is_in_sync():
    """Every guarded-by annotation in the instrumented modules has a
    matching tpustack.sanitize.registry declaration, and vice versa."""
    assert lint_repo(select=["TPL203"]) == []


def test_tpl203_detects_stale_registry_entry(monkeypatch):
    from tpustack.sanitize import registry

    monkeypatch.setitem(
        registry.GUARDED,
        ("tpustack.serving.kv_pool", "KVBlockPool"),
        registry.GUARDED[("tpustack.serving.kv_pool", "KVBlockPool")]
        + (registry.GuardedSpec("_ghost_field", "_lock"),))
    findings = lint_repo(select=["TPL203"])
    msgs = "\n".join(f.message for f in findings)
    assert "_ghost_field" in msgs and "stale" in msgs


def test_tpl203_detects_unregistered_annotation(monkeypatch):
    from tpustack.sanitize import registry

    specs = registry.GUARDED[("tpustack.serving.kv_pool", "KVBlockPool")]
    monkeypatch.setitem(
        registry.GUARDED, ("tpustack.serving.kv_pool", "KVBlockPool"),
        tuple(s for s in specs if s.field != "_free"))
    findings = lint_repo(select=["TPL203"])
    msgs = "\n".join(f.message for f in findings)
    assert "_free" in msgs and "no declaration" in msgs


def test_tpl203_detects_lock_mismatch(monkeypatch):
    from tpustack.sanitize import registry

    key = ("tpustack.models.llm_continuous", "ContinuousEngine")
    monkeypatch.setitem(
        registry.GUARDED, key,
        (registry.GuardedSpec("_fetch_marks", "_wrong_lock"),))
    findings = lint_repo(select=["TPL203"])
    msgs = "\n".join(f.message for f in findings)
    assert "_fetch_marks" in msgs and "disagree" in msgs


def test_tpl203_runtime_optout_requires_note(monkeypatch):
    from tpustack.sanitize import registry

    key = ("tpustack.serving.llm_server", "LLMServer")
    monkeypatch.setitem(
        registry.GUARDED, key,
        (registry.GuardedSpec("_engine", "_lock", writes_only=True,
                              runtime=False, note=""),))
    findings = lint_repo(select=["TPL203"])
    msgs = "\n".join(f.message for f in findings)
    assert "_engine" in msgs and "WHY" in msgs


# ----------------------------------------------- TPL202 blocking-under-lock
def test_tpl202_fires_on_sleep_under_lock(tmp_path):
    found = _lint(tmp_path, """
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def work(self):
                with self._lock:
                    time.sleep(1)
    """, select=["TPL202"])
    assert _codes(found) == ["TPL202"]
    assert "time.sleep" in found[0].message


def test_tpl202_quiet_outside_lock_and_in_nested_def(tmp_path):
    assert _lint(tmp_path, """
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def work(self):
                with self._lock:
                    def deferred():
                        time.sleep(1)  # runs later, off the lock
                    job = deferred
                time.sleep(1)
                return job
    """, select=["TPL202"]) == []


# ---------------------------------------------- TPL301 swallowed-exception
def test_tpl301_fires_on_silent_swallow(tmp_path):
    found = _lint(tmp_path, """
        def f():
            try:
                g()
            except Exception:
                pass
    """, select=["TPL301"])
    assert _codes(found) == ["TPL301"]


def test_tpl301_quiet_when_logged_raised_or_delegated(tmp_path):
    assert _lint(tmp_path, """
        def f(log, waiters):
            try:
                g()
            except Exception:
                log.exception("g failed")
            try:
                g()
            except Exception:
                raise
            try:
                g()
            except Exception as e:
                fail(e)  # delegation: the bound exception is handed on
    """, select=["TPL301"]) == []


# --------------------------------------------------------- TPL302 span-leak
def test_tpl302_fires_on_unended_span(tmp_path):
    found = _lint(tmp_path, """
        def f(tracer):
            span = tracer.start_span("work")
            do_work()
    """, select=["TPL302"])
    assert _codes(found) == ["TPL302"]


def test_tpl302_quiet_on_guaranteed_end_paths(tmp_path):
    assert _lint(tmp_path, """
        def f(tracer):
            span = tracer.start_span("work")
            try:
                do_work()
            finally:
                span.end()

        def g(tracer):
            span = tracer.start_span("work")
            try:
                do_work()
            except Exception:
                span.end(status="error")
                raise
            span.end()

        def h(tracer):
            span = tracer.start_span("work")
            return span  # ownership transferred to the caller

        def w(tracer):
            span = tracer.start_span("work")
            with span:
                do_work()
    """, select=["TPL302"]) == []


# ------------------------------------------------------ TPL401 raw-env-read
def test_tpl401_fires_on_raw_knob_read(tmp_path):
    found = _lint(tmp_path, """
        import os

        a = os.environ.get("TPUSTACK_FOO", "")
        b = os.environ["LLM_BAR"]
        c = os.getenv("TPUSTACK_BAZ")
    """, select=["TPL401"])
    assert len(found) == 3 and _codes(found) == ["TPL401"]


def test_tpl401_quiet_on_registry_reads_and_env_writes(tmp_path):
    assert _lint(tmp_path, """
        import os

        from tpustack.utils import knobs

        a = knobs.get_bool("TPUSTACK_PREFIX_CACHE")
        b = os.environ.get("SOME_OTHER_VAR", "")
        os.environ["TPUSTACK_FOO"] = "1"  # configuring a child process
    """, select=["TPL401"]) == []


# --------------------------------------------- TPL402 knob-registry-drift
def test_tpl402_repo_is_in_sync():
    assert lint_repo(select=["TPL402"]) == []


def test_tpl402_detects_drift(monkeypatch):
    from tpustack.utils import knobs

    monkeypatch.setitem(
        knobs.REGISTRY, "TPUSTACK_GHOST",
        knobs.Knob("TPUSTACK_GHOST", int, 0, "declared but never read"))
    findings = lint_repo(select=["TPL402"])
    msgs = "\n".join(f.message for f in findings)
    assert "TPUSTACK_GHOST" in msgs
    assert "never read" in msgs or "no row" in msgs


# ------------------------------------- TPL501/TPL601 migrated checkers
def test_tpl501_metric_checker_green_and_fires(monkeypatch):
    assert lint_repo(select=["TPL501"]) == []
    from tpustack.obs.catalog import MetricSpec

    monkeypatch.setattr(
        "tpustack.obs.catalog.CATALOG",
        (MetricSpec("vllm_outsider_total", "counter", "h", unit="total"),))
    findings = lint_repo(select=["TPL501"])
    assert findings and all(f.code == "TPL501" for f in findings)


def test_tpl601_manifest_checker_green():
    assert lint_repo(select=["TPL601"]) == []


# --------------------------------------- TPL502 unbounded-tenant-label
def test_tpl502_fires_on_direct_tenant_label(tmp_path):
    found = _lint(tmp_path, """
        def charge(metrics, tenant):
            metrics["tpustack_tenant_chip_seconds_total"].labels(
                server="llm", tenant=tenant).inc(1.0)
    """, select=["TPL502"])
    assert _codes(found) == ["TPL502"]
    assert "TenantLedger" in found[0].message


def test_tpl502_quiet_on_other_labels_and_in_ledger(tmp_path):
    # non-tenant labels are not this rule's business
    assert _lint(tmp_path, """
        def count(metrics):
            metrics["tpustack_http_requests_total"].labels(
                server="llm", endpoint="/x", status="200").inc()
    """, select=["TPL502"]) == []
    # the accounting module itself is the sanctioned writer
    led = tmp_path / "tpustack" / "obs"
    led.mkdir(parents=True)
    f = led / "accounting.py"
    f.write_text("def w(m, t):\n    m.labels(tenant=t).inc()\n")
    assert lint_files([str(f)], root=tmp_path, select=["TPL502"],
                      unscoped=True) == []


def test_tpl502_repo_is_clean():
    """The repo's only tenant-label writer is the ledger (the invariant
    that keeps the tenant cardinality bound unbypassable)."""
    assert lint_repo(select=["TPL502"]) == []


# ----------------------------------------------------------- suppressions
def test_line_suppression(tmp_path):
    src = """
        def f():
            try:
                g()
            except Exception:  # tpulint: disable=TPL301 — reviewed
                pass
    """
    assert _lint(tmp_path, src, select=["TPL301"]) == []


def test_line_suppression_with_uppercase_justification(tmp_path):
    """The code list must end at the first non-code token — a justification
    starting with an uppercase word must not break the suppression."""
    src = """
        def f():
            try:
                g()
            except Exception:  # tpulint: disable=TPL301 OK: reviewed race
                pass
    """
    assert _lint(tmp_path, src, select=["TPL301"]) == []


def test_file_suppression(tmp_path):
    src = """
        # tpulint: disable-file=TPL301

        def f():
            try:
                g()
            except Exception:
                pass
    """
    assert _lint(tmp_path, src, select=["TPL301"]) == []


def test_suppression_is_code_specific(tmp_path):
    src = """
        def f():
            try:
                g()
            except Exception:  # tpulint: disable=TPL999
                pass
    """
    assert _codes(_lint(tmp_path, src, select=["TPL301"])) == ["TPL301"]


def test_unparseable_file_is_a_finding(tmp_path):
    found = _lint(tmp_path, "def broken(:\n", select=["TPL"])
    assert _codes(found) == ["TPL000"]


# ------------------------------------------------------------- CLI surface
def test_cli_json_output(tmp_path, capsys):
    f = tmp_path / "bad.py"
    f.write_text("def f():\n    try:\n        g()\n"
                 "    except Exception:\n        pass\n")
    rc = tpulint_main([str(f), "--no-scope", "--select", "TPL301",
                       "--json", "--root", str(tmp_path)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["count"] == 1
    (finding,) = out["findings"]
    assert finding["code"] == "TPL301"
    assert finding["path"] == "bad.py"
    assert finding["line"] == 4


def test_cli_github_format(tmp_path, capsys):
    """--format=github emits one ::error workflow command per finding,
    with %/newline escaping so multi-line messages stay one command."""
    f = tmp_path / "bad.py"
    f.write_text("def f():\n    try:\n        g()\n"
                 "    except Exception:\n        pass\n")
    rc = tpulint_main([str(f), "--no-scope", "--select", "TPL301",
                       "--format", "github", "--root", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    (line,) = [l for l in out.splitlines() if l.startswith("::error")]
    assert line.startswith("::error file=bad.py,line=4,title=TPL301::")
    assert "\n" not in line and "swallows" in line


def test_cli_github_format_clean_repo_fixture(tmp_path, capsys):
    f = tmp_path / "ok.py"
    f.write_text("def f():\n    return 1\n")
    rc = tpulint_main([str(f), "--no-scope", "--format", "github",
                       "--root", str(tmp_path)])
    assert rc == 0
    assert "::error" not in capsys.readouterr().out


def test_cli_nonexistent_path_is_usage_error(tmp_path, capsys):
    """A typo'd path must exit 2, not print 'clean' over zero files."""
    rc = tpulint_main([str(tmp_path / "no_such_dir"),
                       "--root", str(tmp_path)])
    assert rc == 2
    assert "no such path" in capsys.readouterr().err


def test_cli_list_rules(capsys):
    assert tpulint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in ("TPL101", "TPL102", "TPL201", "TPL202", "TPL203",
                 "TPL301", "TPL302", "TPL401", "TPL402", "TPL501",
                 "TPL601"):
        assert code in out


def test_cli_list_knobs_matches_registry(capsys):
    from tpustack.utils import knobs

    assert tpulint_main(["--list-knobs"]) == 0
    out = capsys.readouterr().out
    assert out.strip() == knobs.markdown_table().strip()
    for name in knobs.REGISTRY:
        assert f"`{name}`" in out


def test_every_rule_has_doc_row():
    """docs/LINTING.md documents every registered rule code."""
    doc = open(os.path.join(REPO, "docs", "LINTING.md")).read()
    for rule in all_rules():
        assert rule.code in doc, f"{rule.code} missing from docs/LINTING.md"


# ------------------------------------------------------------ tier-1 gate
def test_repo_lints_clean_cli():
    """THE gate: shell the unified entrypoint on the repo exactly the way
    CI/operators do and require exit 0.  This one run exercises the AST
    rules, the knob cross-check, and the migrated metric + manifest
    checkers (the old lint_metrics/lint_manifests CLI shell-outs are
    absorbed here)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.tpulint"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout
