"""tpulint gate + checker semantics.

Loads the analysis package exactly the way tools/lint.py does (by file
path, never through lightgbm_tpu/__init__) so these tests also prove
the linter works without importing jax.  Fixture files with deliberate
violations live in tests/fixtures/lint/ — the repo gate never scans
tests/, so they cannot dirty the shipped baseline.
"""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures", "lint")
BASELINE = os.path.join(REPO, "tools", "lint_baseline.json")


def _load_cli():
    name = "_tpulint_cli_under_test"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", "lint.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


CLI = _load_cli()
ana = CLI.load_analysis()


def _run(*names, only=None, root=FIX):
    paths = [os.path.join(root, n) for n in names] or None
    return ana.run_suite(root, paths, only=only)


def _checks(findings):
    return {f.check for f in findings}


# -- the repo gate itself -------------------------------------------------

_repo_findings = None


def repo_findings():
    global _repo_findings
    if _repo_findings is None:
        _repo_findings = ana.run_suite(REPO)
    return _repo_findings


def test_repo_has_zero_high_findings():
    highs = [f for f in repo_findings() if f.severity == "HIGH"]
    assert highs == [], "HIGH findings must be FIXED, never baselined:\n%s" \
        % "\n".join(f.format() for f in highs)


def test_repo_matches_committed_baseline():
    base = ana.baseline.load(BASELINE)
    new, _known, stale = ana.baseline.diff(repo_findings(), base)
    assert new == [], "new lint findings (fix or re-baseline):\n%s" \
        % "\n".join(f.format() for f in new)
    assert stale == [], "stale baseline entries (regenerate with " \
        "tools/lint.py --write-baseline):\n%s" \
        % "\n".join(str(e) for e in stale)


# -- jit/retrace hazards --------------------------------------------------

def test_jit_bad_fixture_fires():
    fs = _run("jit_bad.py")
    assert {"jit-host-sync", "jit-host-cast",
            "jit-traced-branch"} <= _checks(fs)
    syncs = [f for f in fs if f.check == "jit-host-sync"]
    assert len(syncs) == 3 and all(f.severity == "HIGH" for f in syncs)
    # the partial(jax.jit, ...)(impl) wrap form is recognised too
    assert any(f.scope == "wrapped_impl" for f in fs
               if f.check == "jit-traced-branch")
    # static params never count as traced
    branch_names = [f.message for f in fs if f.check == "jit-traced-branch"]
    assert not any("'mode'" in m or "'n'" in m for m in branch_names)


def test_jit_ok_fixture_is_clean():
    assert not [f for f in _run("jit_ok.py")
                if f.check.startswith("jit-")]


# -- lock discipline ------------------------------------------------------

def test_lock_bad_fixture_fires():
    fs = _run("lock_bad.py")
    assert {"lock-unguarded-write", "lock-shared-write",
            "lock-blocking-call", "lock-reentrant",
            "lock-order-cycle"} <= _checks(fs)
    blocking = [f for f in fs if f.check == "lock-blocking-call"]
    assert {f.severity for f in blocking} == {"HIGH", "MEDIUM"}
    unguarded = [f for f in fs if f.check == "lock-unguarded-write"]
    assert any(f.scope == "UnguardedWrite.reset" for f in unguarded)


def test_lock_ok_fixture_is_clean():
    assert not [f for f in _run("lock_ok.py")
                if f.check.startswith("lock-")]


# -- hygiene --------------------------------------------------------------

def test_hygiene_bad_fixture_fires():
    fs = _run("hygiene_bad.py")
    assert {"except-bare", "except-swallow", "resource-no-with",
            "socket-no-with"} <= _checks(fs)


def test_hygiene_ok_fixture_is_clean():
    assert _run("hygiene_ok.py") == []


def test_write_no_fsync_only_inside_package(tmp_path):
    pkg = tmp_path / "lightgbm_tpu"
    pkg.mkdir()
    body = ("def save(path, data):\n"
            "    with open(path, 'w') as fh:\n"
            "        fh.write(data)\n")
    (pkg / "writer.py").write_text(body)
    (pkg / "file_io.py").write_text(body)       # sanctioned home: exempt
    fs = ana.run_suite(str(tmp_path), ["lightgbm_tpu"])
    hits = [f for f in fs if f.check == "write-no-fsync"]
    assert [f.path for f in hits] == ["lightgbm_tpu/writer.py"]


# -- SPMD collective symmetry ---------------------------------------------

def test_collective_bad_fixture_fires():
    fs = [f for f in _run("collective_bad.py")
          if f.check.startswith("collective-")]
    assert {"collective-rank-branch", "collective-divergent-sequence",
            "collective-under-lock"} == _checks(fs)
    assert all(f.severity == "HIGH" for f in fs)
    # the call-graph layer: helper_reduce has no collective name, it is
    # bearing only because it calls allreduce_histograms
    assert any(f.scope == "Comm.transitive_gated" for f in fs
               if f.check == "collective-rank-branch")
    # rank-bounded loops count as rank-dependent control flow too
    assert any(f.scope == "Comm.loop_gated" for f in fs)
    # the divergent if is reported once, not once per call inside it
    assert len([f for f in fs
                if f.check == "collective-divergent-sequence"]) == 1


def test_collective_ok_fixture_is_clean():
    assert not [f for f in _run("collective_ok.py")
                if f.check.startswith("collective-")]


# -- wire protocol --------------------------------------------------------

def test_wire_bad_fixture_fires():
    fs = [f for f in _run("wire_bad.py") if f.check.startswith("wire-")]
    by = {}
    for f in fs:
        by.setdefault(f.check, []).append(f)
    assert set(by) == {"wire-unhandled-kind", "wire-unfenced-recv",
                       "wire-blocking-handler", "wire-dead-kind"}
    assert "FRAME_PING" in by["wire-unhandled-kind"][0].message
    assert by["wire-unhandled-kind"][0].severity == "HIGH"
    assert "FRAME_RETIRED" in by["wire-dead-kind"][0].message
    assert by["wire-dead-kind"][0].severity == "LOW"
    assert {f.scope for f in by["wire-unfenced-recv"]} == \
        {"drain", "ctrl_loop"}
    assert by["wire-blocking-handler"][0].scope == "ctrl_loop"


def test_wire_ok_fixture_is_clean():
    # the fenced/timeout handlers pass outright; the pre-formation
    # handshake passes through its inline disable-next-line — the
    # suppression machinery applies to the new families unchanged
    assert not [f for f in _run("wire_ok.py")
                if f.check.startswith("wire-")]


# -- buffer donation ------------------------------------------------------

def test_donation_bad_fixture_fires():
    fs = [f for f in _run("donation_bad.py")
          if f.check.startswith("donation-")]
    assert {"donation-use-after", "donation-double",
            "donation-escape"} == _checks(fs)
    assert all(f.severity == "HIGH" for f in fs)
    doubles = [f for f in fs if f.check == "donation-double"]
    assert {f.scope for f in doubles} == \
        {"double_same_call", "double_sequential"}
    # attr-cached donating jits track through dict-key bindings
    assert any(f.scope == "Trainer.step" and "state['arena']" in f.message
               for f in fs if f.check == "donation-escape")


def test_donation_ok_fixture_is_clean():
    assert not [f for f in _run("donation_ok.py")
                if f.check.startswith("donation-")]


# -- metrics hygiene ------------------------------------------------------

def test_metrics_bad_fixture_fires():
    fs = [f for f in _run("metrics_bad.py")
          if f.check.startswith("metrics-")]
    assert {"metrics-name-prefix", "metrics-unbounded-label",
            "metrics-dynamic-name"} == _checks(fs)
    prefix = [f for f in fs if f.check == "metrics-name-prefix"]
    assert len(prefix) == 2 and all(f.severity == "HIGH" for f in prefix)
    # all three formatted-string shapes are caught: f-string, %, .format
    labels = [f for f in fs if f.check == "metrics-unbounded-label"]
    assert len(labels) == 3 and all(f.severity == "MEDIUM" for f in labels)


def test_metrics_ok_fixture_is_clean():
    assert not [f for f in _run("metrics_ok.py")
                if f.check.startswith("metrics-")]


# -- seeded-bug regression: the checkers catch real-code mutations --------

def _real(src):
    return os.path.join(REPO, src)


def test_seeded_rank_conditional_collective_is_caught(tmp_path):
    src = open(_real("lightgbm_tpu/parallel/distributed.py")).read()
    probe = '            return self._allgather_impl(' \
            'payload, None, _ZERO_TRACE, 0, "")\n'
    assert probe in src
    clean = tmp_path / "clean"
    seeded = tmp_path / "seeded"
    for d in (clean, seeded):
        d.mkdir()
    (clean / "distributed.py").write_text(src)
    (seeded / "distributed.py").write_text(src.replace(
        probe,
        '            if self.rank == 0:\n'
        '                return self._allgather_impl('
        'payload, None, _ZERO_TRACE, 0, "")\n'
        '            return [payload]\n'))
    assert not [f for f in ana.run_suite(str(clean), ["distributed.py"],
                                         only=["collectives"])
                if f.check.startswith("collective-")]
    hits = [f for f in ana.run_suite(str(seeded), ["distributed.py"],
                                     only=["collectives"])
            if f.check == "collective-rank-branch"]
    assert hits and all(f.severity == "HIGH" for f in hits)
    assert any("_allgather_impl" in f.message for f in hits)


def test_seeded_read_after_donate_is_caught(tmp_path):
    bench = open(os.path.join(FIX, "donation_bench.py")).read()
    probe = "            arrays, out_ids, arena, _ = gp.grow_tree_partition("
    tail = "                interpret=interp)\n"
    assert probe in bench and tail in bench
    seeded = bench.replace(
        probe,
        "            arrays, out_ids, arena_next, _ = "
        "gp.grow_tree_partition(").replace(
        tail, tail + "            checksum = arena.sum()\n")
    for name, text in [
            ("donation_bench.py", seeded),
            ("grow_partition.py",
             open(_real("lightgbm_tpu/ops/grow_partition.py")).read())]:
        (tmp_path / name).write_text(text)
    assert not [f for f in ana.run_suite(
        str(tmp_path), ["."], only=["donation"])
        if f.check.startswith("donation-")
        and f.path == "grow_partition.py"]
    hits = [f for f in ana.run_suite(str(tmp_path), ["."],
                                     only=["donation"])
            if f.check == "donation-use-after"]
    assert hits and all(f.severity == "HIGH" for f in hits)
    assert any("arena" in f.message and f.path == "donation_bench.py"
               for f in hits)


def test_hybrid_leader_dispatch_is_exempt(tmp_path):
    """The is_leader branch inside Hybrid* classes is symmetric by
    construction (one wire exchange per host either way) — exempt; the
    IDENTICAL pattern in any other class still fires."""
    body = ("""class %s:
    def __init__(self):
        self.is_leader = False

    def op(self, arr):
        if self.is_leader:
            out = self.allgather_rows(arr)
        else:
            out = self.await_leader(arr)
        return out

    def allgather_rows(self, arr):
        return [arr]

    def await_leader(self, arr):
        return arr
""")
    hyb = tmp_path / "hyb"
    other = tmp_path / "other"
    for d, cls in ((hyb, "HybridAxisProbe"), (other, "SocketAxisProbe")):
        d.mkdir()
        (d / "probe.py").write_text(body % cls)
    assert not [f for f in ana.run_suite(str(hyb), ["probe.py"],
                                         only=["collectives"])
                if f.check.startswith("collective-")]
    hits = [f for f in ana.run_suite(str(other), ["probe.py"],
                                     only=["collectives"])
            if f.check == "collective-rank-branch"]
    assert hits, "leader branch outside Hybrid* must still fire"


# -- config drift ---------------------------------------------------------

def test_config_drift_fixture_project():
    fs = ana.run_suite(os.path.join(FIX, "driftproj"), ["."])
    by = {f.check: f for f in fs}
    assert set(by) == {"config-dead-param", "config-undocumented-param",
                       "config-stale-doc", "config-broken-alias",
                       "config-phantom-param"}
    assert by["config-dead-param"].scope == "tpu_dead_knob"
    assert by["config-undocumented-param"].scope == "serve_undocumented"
    assert by["config-undocumented-param"].severity == "HIGH"
    assert by["config-stale-doc"].scope == "tpu_removed_knob"
    assert by["config-stale-doc"].path == "docs/Parameters.md"
    assert by["config-broken-alias"].scope == "bad_alias"
    assert "tpu_typo_knob" in by["config-phantom-param"].message


def test_repo_schema_has_no_dead_or_undocumented_params():
    assert not [f for f in repo_findings()
                if f.check.startswith("config-")]


# -- fingerprints and baseline --------------------------------------------

def test_fingerprints_stable_across_runs():
    a = {f.fingerprint: f.check for f in _run("lock_bad.py")}
    b = {f.fingerprint: f.check for f in _run("lock_bad.py")}
    assert a == b and a


@pytest.mark.parametrize("fixture", [
    "lock_bad.py", "collective_bad.py", "wire_bad.py", "donation_bad.py"])
def test_fingerprints_survive_file_moves(tmp_path, fixture):
    src = os.path.join(FIX, fixture)
    flat = tmp_path / "proj1"
    nested = tmp_path / "proj2"
    flat.mkdir()
    (nested / "deep" / "inner").mkdir(parents=True)
    shutil.copy(src, flat / fixture)
    shutil.copy(src, nested / "deep" / "inner" / fixture)
    fp1 = {f.fingerprint for f in ana.run_suite(str(flat), ["."])}
    fp2 = {f.fingerprint for f in ana.run_suite(str(nested), ["."])}
    assert fp1 == fp2 and fp1


def test_baseline_roundtrip(tmp_path):
    fs = _run("lock_bad.py")
    path = str(tmp_path / "base.json")
    ana.baseline.save(path, fs)
    loaded = ana.baseline.load(path)
    new, known, stale = ana.baseline.diff(fs, loaded)
    assert new == [] and stale == [] and len(known) == len(fs)
    # dropping a finding surfaces exactly one stale ledger entry
    new, known, stale = ana.baseline.diff(fs[1:], loaded)
    assert new == [] and len(stale) == 1
    # an empty baseline fails everything
    new, _known, _stale = ana.baseline.diff(fs, {})
    assert len(new) == len(fs)


def test_baseline_rejects_malformed(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"tool": "other"}')
    with pytest.raises(ValueError):
        ana.baseline.load(str(p))
    p.write_text('{"tool": "tpulint", "version": 99, "findings": []}')
    with pytest.raises(ValueError):
        ana.baseline.load(str(p))


# -- suppressions and selection -------------------------------------------

_RACY = ("import threading\n"
         "class C:\n"
         "    def __init__(self):\n"
         "        self._lock = threading.Lock()\n"
         "        self._x = 0\n"
         "    def locked(self):\n"
         "        with self._lock:\n"
         "            self._x += 1\n"
         "    def racy(self):\n"
         "%s"
         "        self._x = 5\n")


def test_disable_next_line_suppression(tmp_path):
    flagged = tmp_path / "a.py"
    flagged.write_text(_RACY % "")
    fs = ana.run_suite(str(tmp_path), ["a.py"])
    assert "lock-unguarded-write" in _checks(fs)
    ok = tmp_path / "b.py"
    ok.write_text(_RACY %
                  "        # tpulint: disable-next-line="
                  "lock-unguarded-write\n")
    fs = ana.run_suite(str(tmp_path), ["b.py"])
    assert "lock-unguarded-write" not in _checks(fs)


def test_only_filter_limits_checker_families():
    fs = _run("lock_bad.py", "hygiene_bad.py", only=["hygiene"])
    assert fs and not [f for f in fs if f.check.startswith("lock-")]


def test_parse_error_becomes_finding(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    fs = ana.run_suite(str(tmp_path), ["broken.py"])
    assert [f.check for f in fs] == ["parse-error"]
    assert fs[0].severity == "HIGH"


# -- the CLI, without jax -------------------------------------------------

def _cli(args, env_extra=None, poison_jax=True, tmp_path=None):
    """Run tools/lint.py in a subprocess with -S (no site imports) and
    a poisoned `jax` module on PYTHONPATH: any jax import anywhere in
    the lint path explodes loudly."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    if poison_jax:
        poison = tmp_path / "poison"
        poison.mkdir(exist_ok=True)
        (poison / "jax.py").write_text(
            "raise RuntimeError('tpulint must not import jax')\n")
        env["PYTHONPATH"] = str(poison)
    return subprocess.run(
        [sys.executable, "-S", os.path.join(REPO, "tools", "lint.py")]
        + args, capture_output=True, text=True, env=env, cwd=REPO)


@pytest.mark.slow
def test_cli_gate_passes_on_shipped_tree(tmp_path):
    res = _cli(["--baseline", BASELINE], tmp_path=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 new" in res.stdout


def test_cli_gate_fails_on_violation_file(tmp_path):
    res = _cli(["--root", FIX, "--baseline", BASELINE, "lock_bad.py"],
               tmp_path=tmp_path)
    assert res.returncode == 1, res.stdout + res.stderr


def test_cli_json_report(tmp_path):
    res = _cli(["--root", FIX, "--json", "jit_bad.py"], tmp_path=tmp_path)
    doc = json.loads(res.stdout)
    assert doc["tool"] == "tpulint"
    assert doc["total"] == len(doc["findings"]) > 0
    assert {f["check"] for f in doc["findings"]} >= {"jit-host-sync"}


@pytest.mark.parametrize("family,fixture,check", [
    ("collectives", "collective_bad.py", "collective-rank-branch"),
    ("wireproto", "wire_bad.py", "wire-unhandled-kind"),
    ("donation", "donation_bad.py", "donation-use-after"),
])
def test_cli_new_families_run_without_jax(tmp_path, family, fixture,
                                          check):
    """The poisoned-jax proof extended to the v2 checkers: each family
    runs in a subprocess where any jax import raises."""
    res = _cli(["--root", FIX, "--json", "--only", family, fixture],
               tmp_path=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    checks = {f["check"] for f in doc["findings"]}
    assert check in checks
    assert all(c.startswith(check.split("-")[0] + "-") for c in checks)


def test_cli_changed_mode(tmp_path):
    # in the repo checkout: exits 0 whether or not files are dirty
    # (dirty files are scanned against the same baseline CI uses)
    res = _cli(["--changed", "--baseline", BASELINE], tmp_path=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    # outside a git checkout: a hard usage error, not a silent pass
    res = subprocess.run(
        [sys.executable, "-S", os.path.join(REPO, "tools", "lint.py"),
         "--changed", "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=str(tmp_path))
    assert res.returncode == 2
    assert "git" in res.stderr


def test_cli_changed_rejects_explicit_paths(tmp_path):
    res = _cli(["--changed", "lock_bad.py"], tmp_path=tmp_path)
    assert res.returncode == 2


def test_smoke_reports_per_family_counts():
    line = CLI.smoke()
    assert line.startswith("lint ")
    for family in ("jit", "locks", "config", "hygiene", "collectives",
                   "wireproto", "donation"):
        assert re.search(r"\b%s \d+\b" % family, line), line
