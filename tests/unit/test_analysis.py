"""Tests for the `trtpu check` static-analysis engine.

One true-positive, one suppressed, and one clean fixture per rule, plus
baseline round-trip, CLI exit codes, and the registry-contract check run
against the REAL provider/transformer/parser registries (that last one
is the compile-time guard the registries themselves can't provide).
"""

import ast
import json
import textwrap

import pytest

from transferia_tpu.analysis import baseline as baseline_mod
from transferia_tpu.analysis.engine import (
    Finding,
    Suppressions,
    run_rules,
)
from transferia_tpu.analysis.rules import (
    DevicePurityRule,
    ExceptionHygieneRule,
    KnobRegistryRule,
    LockDisciplineRule,
    LockOrderRule,
    RegistryContractRule,
    ResourceSafetyRule,
    ThreadLifecycleRule,
)


def check_src(rule, src, path="transferia_tpu/ops/fixture.py"):
    """Run one rule over a snippet, honoring pragmas like the engine."""
    src = textwrap.dedent(src)
    tree = ast.parse(src)
    supp = Suppressions.scan(src)
    if not rule.applies_to(path):
        return []
    return [f for f in rule.check_file(path, tree, src.splitlines())
            if not supp.suppressed(f)]


# -- TPU001 device purity ---------------------------------------------------

TPU_BAD = """
    import jax, functools

    @functools.partial(jax.jit, static_argnums=(1,))
    def kernel(x, n):
        if x > 0:          # data-dependent branch
            return x.item()  # host sync
        return x
"""

TPU_SUPPRESSED = """
    import jax

    @jax.jit
    def kernel(x):
        return x.item()  # trtpu: ignore[TPU001]
"""

TPU_CLEAN = """
    import jax, jax.numpy as jnp, functools

    @functools.partial(jax.jit, static_argnums=(1,))
    def kernel(x, n):
        if n > 2:              # static arg: concrete at trace time
            x = x * 2
        if x.ndim == 2:        # shape metadata: trace-time concrete
            x = x.sum(axis=1)
        return jnp.where(x > 0, x, -x)
"""


class TestDevicePurity:
    def test_true_positive(self):
        found = check_src(DevicePurityRule(), TPU_BAD)
        assert len(found) == 2
        msgs = " ".join(f.message for f in found)
        assert "data-dependent" in msgs and ".item()" in msgs
        assert all(f.rule == "TPU001" and f.severity == "error"
                   for f in found)

    def test_suppressed(self):
        assert check_src(DevicePurityRule(), TPU_SUPPRESSED) == []

    def test_clean(self):
        assert check_src(DevicePurityRule(), TPU_CLEAN) == []

    def test_jit_call_idiom(self):
        # fn = jax.jit(program) — the dominant idiom in ops/fused.py
        src = """
            import jax

            def program(a, flag):
                return float(a) if flag else a

            fn = jax.jit(program, static_argnames="flag")
        """
        found = check_src(DevicePurityRule(), src)
        assert [f.message.split("(")[0].strip() for f in found] == \
            ["float"]

    def test_out_of_scope_path_ignored(self):
        # host-side modules may branch on values after device_get
        found = check_src(DevicePurityRule(), TPU_BAD,
                          path="transferia_tpu/runtime/local.py")
        assert found == []


# -- LCK001 lock discipline -------------------------------------------------

LCK_BAD = """
    import threading, time

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def inc(self):
            with self._lock:
                self.n += 1
                time.sleep(0.1)

        def reset(self):
            self.n = 0
"""

LCK_SUPPRESSED = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def inc(self):
            with self._lock:
                self.n += 1

        def reset_unsafe(self):
            self.n = 0  # trtpu: ignore[LCK001]
"""

LCK_CLEAN = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def inc(self):
            with self._lock:
                self._inc_locked()

        def _inc_locked(self):
            self.n += 1   # _locked suffix: caller holds the lock
"""


class TestLockDiscipline:
    def test_true_positive(self):
        found = check_src(LockDisciplineRule(), LCK_BAD)
        kinds = sorted(f.severity for f in found)
        assert kinds == ["error", "warning"]  # racy write + sleep
        racy = [f for f in found if f.severity == "error"][0]
        assert "Counter.n" in racy.message

    def test_suppressed(self):
        assert check_src(LockDisciplineRule(), LCK_SUPPRESSED) == []

    def test_clean_locked_convention(self):
        assert check_src(LockDisciplineRule(), LCK_CLEAN) == []

    def test_blocking_call_in_with_header(self):
        # the connect in the with-items runs while the lock is held;
        # `with connect(), self._lock:` (acquired after) does not
        src = """
            import socket, threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock, socket.create_connection(("h", 1)) as s:
                        return s

                def ok(self):
                    with socket.create_connection(("h", 1)) as s, self._lock:
                        return s
        """
        found = check_src(LockDisciplineRule(), src)
        assert len(found) == 1
        assert "create_connection" in found[0].message

    def test_no_lock_no_findings(self):
        src = """
            class Plain:
                def set(self, v):
                    self.v = v
        """
        assert check_src(LockDisciplineRule(), src) == []


# -- EXC001 exception hygiene -----------------------------------------------

EXC_BAD = """
    def f():
        try:
            g()
        except Exception:
            pass
"""

EXC_SUPPRESSED = """
    def f():
        try:
            g()
        except Exception:  # trtpu: ignore[EXC001]
            pass  # best-effort teardown
"""

EXC_CLEAN = """
    import logging

    def f():
        try:
            g()
        except Exception as e:
            logging.getLogger(__name__).debug("g failed: %s", e)
"""


class TestExceptionHygiene:
    def test_true_positive(self):
        found = check_src(ExceptionHygieneRule(), EXC_BAD)
        assert len(found) == 1 and found[0].rule == "EXC001"

    def test_suppressed(self):
        assert check_src(ExceptionHygieneRule(), EXC_SUPPRESSED) == []

    def test_clean(self):
        assert check_src(ExceptionHygieneRule(), EXC_CLEAN) == []

    def test_bare_except_flagged(self):
        src = """
            def f():
                try:
                    g()
                except:
                    continue_ = None
                    pass
        """
        # non-noop body that neither logs nor raises is NOT flagged
        # (only silent swallows and device-dispatch wraps are)
        assert check_src(ExceptionHygieneRule(), src) == []

    def test_device_dispatch_wrap(self):
        src = """
            def f(mesh, batch):
                try:
                    out = mesh.device_dispatch(batch)
                except Exception:
                    out = None
                return out
        """
        found = check_src(ExceptionHygieneRule(), src)
        assert len(found) == 1
        assert "device dispatch" in found[0].message


# -- NET001 resource safety -------------------------------------------------

NET_BAD = """
    import socket, json

    def f(path):
        s = socket.create_connection(("host", 9000))
        return json.load(open(path))
"""

NET_SUPPRESSED = """
    import socket

    def f():
        s = socket.create_connection(("host", 9000))  # trtpu: ignore[NET001]
        return s
"""

NET_CLEAN = """
    import socket, json

    def f(path):
        s = socket.create_connection(("host", 9000), timeout=30.0)
        with open(path) as fh:
            return json.load(fh)
"""


class TestResourceSafety:
    def test_true_positive(self):
        found = check_src(ResourceSafetyRule(), NET_BAD)
        assert len(found) == 2
        msgs = " ".join(f.message for f in found)
        assert "timeout" in msgs and "with open" in msgs

    def test_suppressed(self):
        assert check_src(ResourceSafetyRule(), NET_SUPPRESSED) == []

    def test_clean(self):
        assert check_src(ResourceSafetyRule(), NET_CLEAN) == []

    def test_http_connection_without_timeout(self):
        src = """
            import http.client

            def f(host):
                return http.client.HTTPSConnection(host)
        """
        found = check_src(ResourceSafetyRule(), src)
        assert len(found) == 1 and "HTTPSConnection" in found[0].message


# -- REG001 registry contract -----------------------------------------------

class TestRegistryContract:
    def _project_findings(self, sources: dict[str, str]):
        rule = RegistryContractRule()
        rule.do_import_check = False
        files = {}
        for path, src in sources.items():
            src = textwrap.dedent(src)
            files[path] = (ast.parse(src), src.splitlines())
        return rule.check_project("/tmp", files)

    def test_duplicate_transformer_key(self):
        found = self._project_findings({
            "a.py": """
                @register_transformer("mask_field")
                class A:
                    pass
            """,
            "b.py": """
                @register_transformer("mask_field")
                class B:
                    pass
            """,
        })
        assert len(found) == 1
        assert "duplicate transformer key 'mask_field'" in found[0].message

    def test_provider_without_name(self):
        found = self._project_findings({
            "p.py": """
                @register_provider
                class P:
                    pass
            """,
        })
        assert len(found) == 1 and "without a literal NAME" \
            in found[0].message

    def test_unique_keys_clean(self):
        found = self._project_findings({
            "a.py": """
                @register_transformer("x")
                class A:
                    pass

                @register_parser("x")
                class B:
                    pass
            """,
        })
        assert found == []  # same key, different registries: fine

    def test_real_registries_hold_contract(self):
        """The load pass against the actual provider/transformer/parser
        registries: unique keys, concrete classes, NAME == key."""
        findings = RegistryContractRule().import_check()
        assert findings == [], [f.message for f in findings]

    def test_real_tree_has_no_duplicate_keys(self):
        result = run_rules(["transferia_tpu"],
                           [_no_import_reg()], root=_repo_root())
        assert result.findings == [], \
            [f.format() for f in result.findings]


def _no_import_reg():
    rule = RegistryContractRule()
    rule.do_import_check = False
    return rule


def _repo_root():
    import os

    import transferia_tpu

    return os.path.dirname(os.path.dirname(transferia_tpu.__file__))


# -- engine plumbing --------------------------------------------------------

class TestSuppressions:
    def test_file_level(self):
        src = "# trtpu: ignore-file[EXC001]\nx = 1\n"
        supp = Suppressions.scan(src)
        assert supp.suppressed(Finding("EXC001", "warning", "f.py",
                                       2, 1, "m"))
        assert not supp.suppressed(Finding("NET001", "warning", "f.py",
                                           2, 1, "m"))

    def test_bare_ignore_suppresses_all(self):
        src = "x = 1  # trtpu: ignore\n"
        supp = Suppressions.scan(src)
        assert supp.suppressed(Finding("TPU001", "error", "f.py",
                                       1, 1, "m"))

    def test_wrong_line_does_not_suppress(self):
        src = "x = 1  # trtpu: ignore[EXC001]\ny = 2\n"
        supp = Suppressions.scan(src)
        assert not supp.suppressed(Finding("EXC001", "warning", "f.py",
                                           2, 1, "m"))


class TestBaseline:
    def test_round_trip(self, tmp_path):
        f1 = Finding("EXC001", "warning", "a.py", 10, 1, "m",
                     snippet="except Exception:")
        f2 = Finding("NET001", "warning", "b.py", 4, 1, "m",
                     snippet="open(p)")
        path = str(tmp_path / "base.json")
        assert baseline_mod.save(path, [f1, f2]) == 2
        known = baseline_mod.load(path)
        new, old = baseline_mod.split([f1, f2], known)
        assert new == [] and len(old) == 2

    def test_line_shift_keeps_match(self, tmp_path):
        f1 = Finding("EXC001", "warning", "a.py", 10, 1, "m",
                     snippet="except Exception:")
        path = str(tmp_path / "base.json")
        baseline_mod.save(path, [f1])
        shifted = Finding("EXC001", "warning", "a.py", 99, 1, "m",
                          snippet="except Exception:")
        new, old = baseline_mod.split([shifted],
                                      baseline_mod.load(path))
        assert new == [] and old == [shifted]

    def test_new_finding_detected(self, tmp_path):
        path = str(tmp_path / "base.json")
        baseline_mod.save(path, [])
        fresh = Finding("LCK001", "error", "c.py", 3, 1, "m",
                        snippet="self.x = 1")
        new, old = baseline_mod.split([fresh], baseline_mod.load(path))
        assert len(new) == 1 and old == []

    def test_duplicate_snippets_disambiguated(self):
        a = Finding("EXC001", "warning", "a.py", 5, 1, "m",
                    snippet="except Exception:")
        b = Finding("EXC001", "warning", "a.py", 50, 1, "m",
                    snippet="except Exception:")
        fps = baseline_mod.fingerprints([a, b])
        assert len(set(fps)) == 2


class TestEngineAndCli:
    def test_run_rules_on_fixture_tree(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "bad.py").write_text(textwrap.dedent(EXC_BAD))
        (pkg / "skip.py").write_text(
            "# trtpu: ignore-file[EXC001]\n" + textwrap.dedent(EXC_BAD))
        result = run_rules(["pkg"], [ExceptionHygieneRule()],
                           root=str(tmp_path))
        assert result.files_checked == 2
        assert [f.path for f in result.findings] == ["pkg/bad.py"]

    def test_parse_error_reported_not_fatal(self, tmp_path):
        (tmp_path / "broken.py").write_text("def f(:\n")
        result = run_rules(["broken.py"], [ExceptionHygieneRule()],
                           root=str(tmp_path))
        assert result.files_checked == 0
        assert result.parse_errors[0].rule == "PARSE"

    def test_cli_strict_exit_codes(self, tmp_path, capsys, monkeypatch):
        from transferia_tpu.analysis import cli as check_cli

        bad = tmp_path / "transferia_tpu"
        bad.mkdir()
        (bad / "bad.py").write_text(textwrap.dedent(EXC_BAD))
        monkeypatch.setattr(check_cli, "repo_root", lambda: str(tmp_path))
        # not strict: reports but exits 0
        assert check_cli.main(["--baseline", "none"]) == 0
        out = capsys.readouterr().out
        assert "EXC001" in out and "1 new finding(s)" in out
        # strict: new finding -> 1
        assert check_cli.main(["--strict", "--baseline", "none"]) == 1
        capsys.readouterr()
        # baseline it -> strict passes again
        base = str(tmp_path / "base.json")
        assert check_cli.main(["--update-baseline",
                               "--baseline", base]) == 0
        assert check_cli.main(["--strict", "--baseline", base]) == 0

    def test_cli_json_output(self, tmp_path, capsys, monkeypatch):
        from transferia_tpu.analysis import cli as check_cli

        pkg = tmp_path / "transferia_tpu"
        pkg.mkdir()
        (pkg / "bad.py").write_text(textwrap.dedent(NET_BAD))
        monkeypatch.setattr(check_cli, "repo_root", lambda: str(tmp_path))
        assert check_cli.main(["--json", "--baseline", "none"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert {f["rule"] for f in data["new"]} == {"NET001"}
        assert data["files_checked"] == 1

    def test_update_baseline_refuses_narrowed_run(self, tmp_path,
                                                  capsys, monkeypatch):
        # a subset run must not clobber the tree-wide baseline
        from transferia_tpu.analysis import cli as check_cli

        pkg = tmp_path / "transferia_tpu"
        pkg.mkdir()
        (pkg / "ok.py").write_text("x = 1\n")
        monkeypatch.setattr(check_cli, "repo_root", lambda: str(tmp_path))
        base = str(tmp_path / "base.json")
        assert check_cli.main(["transferia_tpu", "--update-baseline",
                               "--baseline", base]) == 2
        assert check_cli.main(["--rules", "EXC001", "--update-baseline",
                               "--baseline", base]) == 2
        assert "full run" in capsys.readouterr().err
        assert check_cli.main(["--update-baseline",
                               "--baseline", base]) == 0

    def test_cli_unknown_rule(self, capsys):
        from transferia_tpu.analysis.cli import main

        assert main(["--rules", "NOPE42"]) == 2
        assert "unknown rule id" in capsys.readouterr().err

    def test_cli_list_rules(self, capsys):
        from transferia_tpu.analysis.cli import main

        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("TPU001", "LCK001", "EXC001", "NET001", "REG001",
                    "FPT001"):
            assert rid in out

    def test_trtpu_check_subcommand_wired(self, capsys):
        from transferia_tpu.cli.main import main

        assert main(["check", "--list-rules"]) == 0
        assert "TPU001" in capsys.readouterr().out


class TestFailpointContract:
    """FPT001: literal, registered, uniquely-owned failpoint sites."""

    def _project_findings(self, sources: dict[str, str],
                          catalog=("sink.push", "storage.part.read")):
        from transferia_tpu.analysis.rules import FailpointContractRule

        rule = FailpointContractRule()
        rule.known_sites = frozenset(catalog)
        files = {}
        # the dead-entry pass only runs when the catalog file itself is
        # in the analyzed set (narrowed runs can't judge coverage)
        sources.setdefault("transferia_tpu/chaos/sites.py", "SITES = {}\n")
        for path, src in sources.items():
            src = textwrap.dedent(src)
            files[path] = (ast.parse(src), src.splitlines())
        return rule.check_project("/tmp", files)

    def test_clean_tree(self):
        found = self._project_findings({
            "transferia_tpu/a.py": 'failpoint("sink.push")\n',
            "transferia_tpu/b.py":
                'fp.torn_rows("storage.part.read", n)\n',
        })
        assert found == [], [f.message for f in found]

    def test_non_literal_site_name(self):
        found = self._project_findings({
            "transferia_tpu/a.py": 'failpoint("sink.push")\n'
                                   'failpoint(SITE)\n',
            "transferia_tpu/b.py":
                'torn_rows("storage.part.read", n)\n',
        })
        assert len(found) == 1
        assert "string literal" in found[0].message

    def test_unregistered_site(self):
        found = self._project_findings({
            "transferia_tpu/a.py": 'failpoint("sink.push")\n'
                                   'failpoint("made.up.site")\n',
            "transferia_tpu/b.py":
                'torn_rows("storage.part.read", n)\n',
        })
        assert len(found) == 1
        assert "not registered" in found[0].message

    def test_duplicate_ownership(self):
        found = self._project_findings({
            "transferia_tpu/a.py": 'failpoint("sink.push")\n',
            "transferia_tpu/b.py": 'failpoint("sink.push")\n'
                                   'failpoint("storage.part.read")\n',
        })
        assert len(found) == 1
        assert "already instrumented" in found[0].message

    def test_dead_catalog_entry(self):
        found = self._project_findings({
            "transferia_tpu/a.py": 'failpoint("sink.push")\n',
        })
        assert len(found) == 1
        assert "no call site references it" in found[0].message

    def test_chaos_package_and_tests_exempt(self):
        found = self._project_findings({
            "transferia_tpu/chaos/failpoints.py":
                'failpoint(whatever)\n',
            "tests/unit/test_x.py": 'failpoint("bogus.site")\n',
            "transferia_tpu/a.py": 'failpoint("sink.push")\n',
            "transferia_tpu/b.py":
                'torn_rows("storage.part.read", n)\n',
        })
        assert found == [], [f.message for f in found]

    def test_real_tree_holds_contract(self):
        """Every instrumented site in the real tree is literal,
        registered, uniquely owned, and no catalog entry is dead."""
        from transferia_tpu.analysis.rules import FailpointContractRule

        result = run_rules(["transferia_tpu"],
                           [FailpointContractRule()],
                           root=_repo_root())
        assert result.findings == [], \
            [f.format() for f in result.findings]


class TestTraceContract:
    """TRC001: every failpoint site's enclosing function must open a
    span or emit a trace instant so chaos fires land on a timeline."""

    def _findings(self, sources: dict[str, str], allow=()):
        from transferia_tpu.analysis.rules import TraceContractRule

        rule = TraceContractRule()
        rule.allow_untraced = frozenset(allow)
        files = {}
        for path, src in sources.items():
            src = textwrap.dedent(src)
            files[path] = (ast.parse(src), src.splitlines())
        return rule.check_project("/tmp", files)

    def test_untraced_function_flagged(self):
        found = self._findings({"transferia_tpu/a.py": """
            def naked():
                failpoint("some.site")
        """})
        assert len(found) == 1
        assert "opens no span" in found[0].message
        assert found[0].rule == "TRC001"

    def test_span_in_function_passes(self):
        found = self._findings({"transferia_tpu/a.py": """
            def covered():
                failpoint("some.site")
                with trace.span("work"):
                    pass
        """})
        assert found == [], [f.message for f in found]

    def test_instant_in_function_passes(self):
        found = self._findings({"transferia_tpu/a.py": """
            def covered(t):
                failpoint("some.site")
                trace.instant("fired", at=t)
        """})
        assert found == []

    def test_retroactive_complete_passes(self):
        found = self._findings({"transferia_tpu/a.py": """
            def covered(t0, dur):
                failpoint("some.site")
                trace.complete("wait", t0=t0, dur=dur)
        """})
        assert found == []

    def test_adopted_alone_does_not_pass(self):
        # adoption records nothing — the fire still needs a local
        # span/instant for the timeline to show where it landed
        found = self._findings({"transferia_tpu/a.py": """
            def adopted_only(ctx):
                with trace.adopted(ctx):
                    failpoint("some.site")
        """})
        assert len(found) == 1

    def test_torn_rows_sites_also_checked(self):
        found = self._findings({"transferia_tpu/a.py": """
            def naked(n):
                return torn_rows("some.site", n)
        """})
        assert len(found) == 1

    def test_module_level_site_flagged(self):
        found = self._findings({"transferia_tpu/a.py":
                                'failpoint("some.site")\n'})
        assert len(found) == 1
        assert "module level" in found[0].message

    def test_chaos_and_tests_exempt(self):
        found = self._findings({
            "transferia_tpu/chaos/runner.py": """
                def drive():
                    failpoint("some.site")
            """,
            "tests/unit/test_x.py": """
                def test_y():
                    failpoint("some.site")
            """,
        })
        assert found == []

    def test_allowlist_suppresses(self):
        found = self._findings({"transferia_tpu/a.py": """
            def naked():
                failpoint("allowed.site")
        """}, allow=("allowed.site",))
        assert found == []

    def test_non_literal_sites_left_to_fpt001(self):
        found = self._findings({"transferia_tpu/a.py": """
            def naked(site):
                failpoint(site)
        """})
        assert found == []

    def test_real_tree_holds_contract(self):
        from transferia_tpu.analysis.rules import TraceContractRule

        result = run_rules(["transferia_tpu"],
                           [TraceContractRule()],
                           root=_repo_root())
        assert result.findings == [], \
            [f.format() for f in result.findings]


@pytest.mark.slow
class TestWholeTree:
    def test_tree_is_clean_under_committed_baseline(self):
        """Acceptance: `trtpu check --strict` on the real tree."""
        from transferia_tpu.analysis.cli import main

        assert main(["--strict"]) == 0


# -- LCK002 whole-program lock order ------------------------------------------

def project_findings(rule, sources):
    """Run a ProjectRule over in-memory sources keyed by relpath."""
    files = {}
    for path, src in sources.items():
        src = textwrap.dedent(src)
        files[path] = (ast.parse(src), src.splitlines())
    return rule.check_project(".", files)


LCK2_ABBA = """
    import threading

    class Pair:
        def __init__(self):
            self._x = threading.Lock()
            self._y = threading.Lock()

        def fwd(self):
            with self._x:
                with self._y:
                    pass

        def rev(self):
            with self._y:
                with self._x:
                    pass
"""

LCK2_INTERPROC = """
    import threading

    class Pair:
        def __init__(self):
            self._x = threading.Lock()
            self._y = threading.Lock()

        def fwd(self):
            with self._x:
                with self._y:
                    pass

        def rev(self):
            with self._y:
                self.helper()

        def helper(self):
            with self._x:
                pass
"""

LCK2_CLEAN = """
    import threading

    class Pair:
        def __init__(self):
            self._x = threading.Lock()
            self._y = threading.Lock()

        def one(self):
            with self._x:
                with self._y:
                    pass

        def two(self):
            with self._x:
                with self._y:
                    pass
"""

LCK2_COND_ALIAS = """
    import threading

    class Gate:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition(self._lock)

        def f(self):
            with self._cond:
                with self._lock:
                    pass

        def g(self):
            with self._lock:
                with self._cond:
                    pass
"""

LCK2_NAMED = """
    from transferia_tpu.runtime import lockwatch

    class A:
        def __init__(self):
            self._a = lockwatch.named_lock("svc.alpha")
            self._b = lockwatch.named_lock("svc.beta")

        def fwd(self):
            with self._a:
                with self._b:
                    pass

    class B:
        def __init__(self):
            self._p = lockwatch.named_lock("svc.beta")
            self._q = lockwatch.named_lock("svc.alpha")

        def rev(self):
            with self._p:
                with self._q:
                    pass
"""


class TestLockOrder:
    def test_direct_abba_cycle(self):
        found = project_findings(LockOrderRule(),
                                 {"transferia_tpu/pair.py": LCK2_ABBA})
        assert len(found) == 1
        f = found[0]
        assert f.rule == "LCK002" and f.severity == "error"
        assert "potential deadlock" in f.message
        assert "Pair._x" in f.message and "Pair._y" in f.message
        # one witness chain per direction, each file:line -> file:line
        assert f.message.count("before") == 2
        assert f.message.count("pair.py:") >= 4
        assert " -> " in f.message

    def test_interprocedural_cycle_through_call_chain(self):
        found = project_findings(
            LockOrderRule(), {"transferia_tpu/pair.py": LCK2_INTERPROC})
        assert len(found) == 1
        # the y-before-x witness threads rev() -> helper(): the chain
        # carries the call site, so it is at least three steps long
        assert found[0].message.count("pair.py:") >= 5

    def test_consistent_order_is_clean(self):
        assert project_findings(
            LockOrderRule(), {"transferia_tpu/pair.py": LCK2_CLEAN}) == []

    def test_condition_aliases_to_wrapped_lock(self):
        # Condition(self._lock) IS self._lock for ordering purposes:
        # opposite cond/lock nesting must not report a false cycle
        assert project_findings(
            LockOrderRule(),
            {"transferia_tpu/gate.py": LCK2_COND_ALIAS}) == []

    def test_named_locks_unify_identity_across_classes(self):
        found = project_findings(
            LockOrderRule(), {"transferia_tpu/svc.py": LCK2_NAMED})
        assert len(found) == 1
        assert "svc.alpha" in found[0].message
        assert "svc.beta" in found[0].message

    def test_suppressed(self, tmp_path):
        pkg = tmp_path / "transferia_tpu"
        pkg.mkdir()
        body = textwrap.dedent(LCK2_ABBA)
        (pkg / "pair.py").write_text(body)
        result = run_rules(["transferia_tpu"], [LockOrderRule()],
                           root=str(tmp_path))
        assert len(result.findings) == 1
        (pkg / "pair.py").write_text(
            "# trtpu: ignore-file[LCK002]\n" + body)
        result = run_rules(["transferia_tpu"], [LockOrderRule()],
                           root=str(tmp_path))
        assert result.findings == []

    def test_real_tree_lock_graph_is_acyclic(self):
        result = run_rules(["transferia_tpu"], [LockOrderRule()],
                           root=_repo_root())
        assert result.findings == [], \
            [f.format() for f in result.findings]

    def test_real_coordinator_locks_resolved(self):
        """The index must SEE the production locks — an acyclic result
        is only meaningful if resolution worked."""
        import os

        from transferia_tpu.analysis import callgraph
        from transferia_tpu.analysis.engine import iter_python_files

        root = _repo_root()
        files = {}
        for rel in iter_python_files(["transferia_tpu"], root):
            with open(os.path.join(root, rel), encoding="utf-8") as fh:
                src = fh.read()
            try:
                files[rel] = (ast.parse(src), src.splitlines())
            except SyntaxError:
                continue
        ix = callgraph.build_index(files)
        assert "coordinator.op" in ix.locks
        assert "fleet.scheduler" in ix.locks
        assert ix.locks["coordinator.op"].kind == "rlock"
        # acquired-while-holding nesting exists and stays acyclic: the
        # coordinator releases its map locks before taking op locks
        assert len(ix.edges) > 0
        assert callgraph.find_cycles(ix) == []


# -- THD001 thread lifecycle ---------------------------------------------------

THD_BAD = """
    import threading
    from concurrent.futures import ThreadPoolExecutor

    def leak_thread():
        t = threading.Thread(target=print)
        t.start()

    def leak_inline():
        threading.Thread(target=print).start()

    def leak_pool():
        ex = ThreadPoolExecutor(max_workers=2)
        ex.submit(print)

    def leak_timer():
        t = threading.Timer(5.0, print)
        t.start()
"""

THD_SUPPRESSED = """
    import threading

    def monitor():
        t = threading.Thread(target=print)  # trtpu: ignore[THD001]
        t.start()
"""

THD_CLEAN = """
    import threading
    from concurrent.futures import ThreadPoolExecutor

    def joins():
        t = threading.Thread(target=print)
        t.start()
        t.join()

    def daemonized():
        t = threading.Thread(target=print, daemon=True)
        t.start()

    def daemon_attr():
        t = threading.Thread(target=print)
        t.daemon = True
        t.start()

    def pool_ctx():
        with ThreadPoolExecutor(max_workers=2) as ex:
            ex.submit(print)

    def pool_shutdown():
        ex = ThreadPoolExecutor(max_workers=2)
        try:
            ex.submit(print)
        finally:
            ex.shutdown()

    def timer_cancelled():
        t = threading.Timer(5.0, print)
        t.start()
        t.cancel()

    def comprehension_join():
        ts = [threading.Thread(target=print) for _ in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
"""

THD_CLASS_CLEAN = """
    import threading

    class Pump:
        def start(self):
            self._t = threading.Thread(target=self._run)
            self._t.start()

        def stop(self):
            self._t.join()

        def _run(self):
            pass
"""

THD_CLASS_LEAK = """
    import threading

    class Leaky:
        def start(self):
            self._t = threading.Thread(target=print)
            self._t.start()
"""

THD_CROSS_FUNCTION = """
    import threading

    def bad():
        t = threading.Thread(target=print)
        t.start()

    def unrelated():
        t = threading.Thread(target=print)
        t.start()
        t.join()
"""


class TestThreadLifecycle:
    def test_true_positives(self):
        found = check_src(ThreadLifecycleRule(), THD_BAD)
        assert len(found) == 4
        msgs = " ".join(f.message for f in found)
        assert "no visible lifecycle" in msgs
        assert "never bound" in msgs                 # inline .start()
        assert "neither a context manager" in msgs   # executor
        assert all(f.rule == "THD001" and f.severity == "error"
                   for f in found)

    def test_suppressed(self):
        assert check_src(ThreadLifecycleRule(), THD_SUPPRESSED) == []

    def test_clean_lifecycles(self):
        assert check_src(ThreadLifecycleRule(), THD_CLEAN) == []

    def test_class_attr_join_in_other_method_is_clean(self):
        assert check_src(ThreadLifecycleRule(), THD_CLASS_CLEAN) == []

    def test_class_attr_leak_flagged(self):
        found = check_src(ThreadLifecycleRule(), THD_CLASS_LEAK)
        assert len(found) == 1
        assert "'_t'" in found[0].message

    def test_join_in_unrelated_function_does_not_credit(self):
        # ownership is per-scope: a join of a same-named local in a
        # DIFFERENT function must not absolve the leak
        found = check_src(ThreadLifecycleRule(), THD_CROSS_FUNCTION)
        assert len(found) == 1
        assert found[0].line == 5

    def test_real_tree_holds_contract(self):
        result = run_rules(["transferia_tpu"], [ThreadLifecycleRule()],
                           root=_repo_root())
        assert result.findings == [], \
            [f.format() for f in result.findings]


# -- KNB001 env-knob drift -------------------------------------------------------

class TestKnobRegistry:
    def _run(self, tmp_path, files, readme=""):
        (tmp_path / "README.md").write_text(readme)
        for rel, src in files.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
        return run_rules(["transferia_tpu"], [KnobRegistryRule()],
                         root=str(tmp_path)).findings

    def test_direct_environ_read_flagged(self, tmp_path):
        found = self._run(tmp_path, {"transferia_tpu/a.py": """
            import os
            v = os.environ.get("TRANSFERIA_TPU_FOO", "1")
        """}, readme="| `TRANSFERIA_TPU_FOO` | 1 | a knob |\n")
        assert len(found) == 1
        assert "read directly" in found[0].message
        assert "runtime.knobs" in found[0].message

    def test_getenv_and_subscript_read_flagged(self, tmp_path):
        found = self._run(tmp_path, {"transferia_tpu/a.py": """
            import os
            v = os.getenv("TRANSFERIA_TPU_FOO")
            w = os.environ["TRANSFERIA_TPU_FOO"]
        """}, readme="TRANSFERIA_TPU_FOO\n")
        assert len(found) == 2

    def test_environ_write_is_not_a_read(self, tmp_path):
        found = self._run(tmp_path, {"transferia_tpu/a.py": """
            import os
            os.environ["TRANSFERIA_TPU_SET"] = "1"
            del os.environ["TRANSFERIA_TPU_SET"]
        """})
        assert found == []

    def test_registry_helper_documented_is_clean(self, tmp_path):
        found = self._run(tmp_path, {"transferia_tpu/a.py": """
            from transferia_tpu.runtime import knobs
            v = knobs.env_int("TRANSFERIA_TPU_ROWS", 4)
        """}, readme="| `TRANSFERIA_TPU_ROWS` | 4 | rows |\n")
        assert found == []

    def test_undocumented_knob_flagged_once(self, tmp_path):
        found = self._run(tmp_path, {"transferia_tpu/a.py": """
            from transferia_tpu.runtime import knobs
            v = knobs.env_int("TRANSFERIA_TPU_HIDDEN", 4)
            w = knobs.env_float("TRANSFERIA_TPU_HIDDEN", 4.0)
        """})
        assert len(found) == 1
        assert "not documented" in found[0].message
        assert "TRANSFERIA_TPU_HIDDEN" in found[0].message

    def test_dead_doc_row_flagged(self, tmp_path):
        found = self._run(tmp_path, {"transferia_tpu/a.py": """
            from transferia_tpu.runtime import knobs
            v = knobs.env_int("TRANSFERIA_TPU_LIVE", 4)
        """}, readme="| `TRANSFERIA_TPU_LIVE` | 4 | live |\n"
                     "| `TRANSFERIA_TPU_GONE` | 0 | removed |\n")
        assert len(found) == 1
        f = found[0]
        assert f.path == "README.md" and f.line == 2
        assert "dead doc row" in f.message

    def test_env_constant_indirection_resolves(self, tmp_path):
        found = self._run(tmp_path, {"transferia_tpu/a.py": """
            from transferia_tpu.runtime import knobs
            ENV_ROWS = "TRANSFERIA_TPU_ROWS2"
            v = knobs.env_int(ENV_ROWS, 4)
        """})
        assert len(found) == 1
        assert "TRANSFERIA_TPU_ROWS2" in found[0].message

    def test_environ_first_shim_slot_resolves(self, tmp_path):
        # coordinator.interface-style shim: env_float(environ, key, d)
        found = self._run(tmp_path, {"transferia_tpu/a.py": """
            def env_float(environ, key, default):
                return float(environ.get(key, default))

            def read(environ):
                return env_float(environ, "TRANSFERIA_TPU_SHIM", 1.0)
        """}, readme="TRANSFERIA_TPU_SHIM\n")
        assert found == []

    def test_knobs_module_itself_exempt(self, tmp_path):
        found = self._run(tmp_path, {
            "transferia_tpu/runtime/knobs.py": """
                import os
                def env_raw(name, default=None):
                    return os.environ.get(name, default)
                v = os.environ.get("TRANSFERIA_TPU_BASE", "1")
            """}, readme="TRANSFERIA_TPU_BASE\n")
        assert found == []

    def test_suppressed(self, tmp_path):
        found = self._run(tmp_path, {"transferia_tpu/a.py": """
            import os
            v = os.environ.get("TRANSFERIA_TPU_FOO")  # trtpu: ignore[KNB001]
        """}, readme="TRANSFERIA_TPU_FOO\n")
        assert found == []

    def test_a_knob_is_a_transferia_name_inside_the_scan_path(self, tmp_path):
        # the old harness's prefix, which the rule once took for a knob's
        # (spelled in two pieces: no such name is left in the tree)
        other = "BENCH" + "_FOO"
        found = self._run(tmp_path, {
            "transferia_tpu/a.py": f"""
                import os
                v = os.environ.get("{other}", "1")
                w = os.environ.get("TRANSFERIA_TPU_FOO", "1")
            """,
            # a script beside the package: no file outside the scan
            # path is read, so its name keeps no README row alive
            "measure.py": """
                import os
                x = os.environ.get("TRANSFERIA_TPU_BAR", "1")
            """},
            readme=f"{other}\nTRANSFERIA_TPU_FOO\nTRANSFERIA_TPU_BAR\n")
        assert sorted((f.path, f.line) for f in found) == [
            ("README.md", 3), ("transferia_tpu/a.py", 4)]
        assert all(other not in f.message for f in found)

    def test_real_tree_holds_contract(self):
        result = run_rules(["transferia_tpu"], [KnobRegistryRule()],
                           root=_repo_root())
        assert result.findings == [], \
            [f.format() for f in result.findings]
