"""The contract linter (onix/analysis/) — fixture-driven tests per
pass plus the enforcement run over the real tree.

Each pass gets BOTH directions: it fires on the violating fixture tree
(tests/analysis_fixtures/violating/) and stays silent on the fixed
forms (tests/analysis_fixtures/clean/, which also exercises every
exemption mechanism) — so no pass can rot into a no-op and no
exemption can rot into a blanket mute. The final tests run the full
analyzer over the repo itself with an EMPTY baseline: the committed
posture is zero findings, every contract violation fixed or justified
in place."""

import json
import pathlib
import shutil
import subprocess
import sys

from onix.analysis import core, docgen
from onix.analysis.core import AnalysisContext

FIXTURES = pathlib.Path(__file__).parent / "analysis_fixtures"
REPO = pathlib.Path(__file__).parent.parent


def run_fixture(tree: str, only: list[str]) -> list[core.Finding]:
    ctx = AnalysisContext.from_root(FIXTURES / tree)
    return core.run_passes(ctx, only=only)


def messages(findings):
    return "\n".join(f.render() for f in findings)


# -- pass 1: exception discipline ------------------------------------------

def test_excepts_fires_on_silent_swallow():
    found = run_fixture("violating", ["excepts"])
    assert any(f.path == "onix/pipelines/run.py" for f in found), \
        messages(found)


def test_excepts_silent_on_visible_handler():
    assert run_fixture("clean", ["excepts"]) == []


# -- pass 2: env registry ---------------------------------------------------

def test_envs_fires_on_undeclared_read_and_dead_declaration():
    found = run_fixture("violating", ["envs"])
    msgs = messages(found)
    assert "ONIX_FIXTURE_UNDECLARED" in msgs
    assert "ONIX_FIXTURE_DEAD" in msgs
    # The declared-and-read name is NOT a finding.
    assert "ONIX_FIXTURE_DECLARED" not in msgs


def test_envs_silent_when_registry_matches_reads():
    assert run_fixture("clean", ["envs"]) == []


# -- pass 3: counter namespaces --------------------------------------------

def test_counters_fires_on_typo_dead_ns_and_bare_dynamic_key():
    found = run_fixture("violating", ["counters"])
    msgs = messages(found)
    assert "'typo'" in msgs                     # undeclared namespace
    assert "deadns" in msgs                     # dead declaration
    assert "no literal namespace prefix" in msgs
    assert "'used'" not in msgs                 # declared + used: silent


def test_counters_silent_on_clean_tree_with_exemption():
    assert run_fixture("clean", ["counters"]) == []


# -- pass 3b: span registry (r18) ------------------------------------------

def test_spans_fires_on_undeclared_dead_and_dynamic_name():
    found = run_fixture("violating", ["spans"])
    msgs = messages(found)
    assert "'undeclared.span'" in msgs          # opened, not declared
    assert "'dead.span'" in msgs                # declared, never opened
    assert "not a string literal" in msgs       # dynamic name
    assert "'used.span'" not in msgs            # declared + opened: silent


def test_spans_silent_on_clean_tree_with_exemption():
    assert run_fixture("clean", ["spans"]) == []


def test_spans_silent_on_tree_without_tracer(tmp_path):
    # A tree with neither a SPAN_REGISTRY nor tracer calls (plain
    # libraries, the miniature trees other tests stand up) must not
    # produce findings.
    (tmp_path / "m.py").write_text("def f():\n    return 1\n")
    ctx = AnalysisContext.from_root(tmp_path, [tmp_path / "m.py"])
    assert core.run_passes(ctx, only=["spans"]) == []


def test_span_registry_matches_real_tree():
    """Both directions over the repo itself, via the real module (the
    fixture tests prove the pass; this pins the CONTRACT): every
    declared span opens somewhere, every literal open is declared."""
    from onix.utils import telemetry
    ctx = AnalysisContext.from_root(REPO)
    assert core.run_passes(ctx, only=["spans"]) == []
    assert telemetry.SPAN_REGISTRY          # non-empty, really wired


# -- pass 4: gate discipline ------------------------------------------------

def test_gates_fires_on_handrolled_gate_and_offgate_table_consult():
    found = run_fixture("violating", ["gates"])
    msgs = messages(found)
    assert "select_fixture_form" in msgs
    assert "_FIXTURE_MIN_K" in msgs


def test_gates_silent_when_resolved_through_resolve_form_gate():
    assert run_fixture("clean", ["gates"]) == []


# -- pass 5: fingerprint coverage ------------------------------------------

def test_fingerprints_fires_on_uncovered_engine_read():
    found = run_fixture("violating", ["fingerprints"])
    msgs = messages(found)
    assert "mystery_knob" in msgs
    assert "covered_knob" not in msgs           # declared: silent


def test_fingerprints_silent_with_exempt_entry():
    assert run_fixture("clean", ["fingerprints"]) == []


# -- pass 6: jit/trace hazards ---------------------------------------------

def test_tracehaz_fires_on_clock_rng_and_item_in_scan_body():
    found = run_fixture("violating", ["tracehaz"])
    msgs = messages(found)
    assert "time.time" in msgs
    assert "np.random" in msgs
    assert ".item()" in msgs


def test_tracehaz_silent_outside_traced_bodies_and_under_exemption():
    # The clean tree calls time.time() in HOST code around the scan and
    # keeps one in-body trace-time stamp under a justified exemption.
    assert run_fixture("clean", ["tracehaz"]) == []


def test_tracehaz_never_flags_jax_random(tmp_path):
    # jax.random is the device-safe key-stream RNG — the correct tool
    # inside traced code, never a hazard (the first real-tree run's
    # false-positive class, pinned here).
    mod = tmp_path / "onix" / "models"
    mod.mkdir(parents=True)
    (mod / "m.py").write_text(
        "import jax\n"
        "def body(c, x):\n"
        "    return c, jax.random.uniform(jax.random.split(c)[0])\n"
        "def run(xs):\n"
        "    return jax.lax.scan(body, 0, xs)\n")
    ctx = AnalysisContext.from_root(tmp_path)
    assert core.run_passes(ctx, only=["tracehaz"]) == []


# -- pass 7: lock discipline ------------------------------------------------

def test_locks_fires_on_offlock_mutation_only():
    found = run_fixture("violating", ["locks"])
    msgs = messages(found)
    assert "bad_mutation" in msgs
    assert "good_mutation" not in msgs


def test_locks_silent_under_lock_and_holds_annotation():
    assert run_fixture("clean", ["locks"]) == []


# -- pass 8: fault-site / doc drift ----------------------------------------

def test_faultdocs_fires_on_both_drift_directions_and_missing_sections():
    found = run_fixture("violating", ["faultdocs"])
    msgs = messages(found)
    assert "fixture:undocumented" in msgs       # wired, not documented
    assert "doc:only" in msgs                   # documented, not wired
    assert "env-registry" in msgs               # generated section absent


def test_faultdocs_silent_after_write_docs(tmp_path):
    tree = tmp_path / "clean"
    shutil.copytree(FIXTURES / "clean", tree)
    ctx = AnalysisContext.from_root(tree)
    written = docgen.write_docs(ctx)
    assert set(written) == set(docgen.SECTIONS)
    assert core.run_passes(ctx, only=["faultdocs"]) == []
    # Idempotent: a second write changes nothing.
    assert docgen.write_docs(AnalysisContext.from_root(tree)) == []


# -- the exemption mechanism polices itself --------------------------------

def test_exemption_without_justification_is_a_finding(tmp_path):
    (tmp_path / "m.py").write_text(
        "def f():\n"
        "    try:\n"
        "        pass\n"
        "    # lint: exempt[excepts]\n"
        "    except Exception:\n"
        "        pass\n")
    ctx = AnalysisContext.from_root(tmp_path, [tmp_path / "m.py"])
    found = core.run_passes(ctx, only=["excepts"])
    assert any("no justification" in f.message for f in found), \
        messages(found)


def test_exemption_syntax_quoted_in_a_string_is_inert(tmp_path):
    # Annotations are parsed from COMMENT tokens: a string literal
    # quoting the exemption syntax on the line above a violation must
    # neither suppress the finding nor register as a stale exemption
    # (review fix, r17).
    (tmp_path / "m.py").write_text(
        "def f():\n"
        "    try:\n"
        '        x = "# lint: exempt[excepts] -- quoted, not a comment"\n'
        "    except Exception:\n"
        "        pass\n")
    ctx = AnalysisContext.from_root(tmp_path, [tmp_path / "m.py"])
    found = core.run_passes(ctx, only=["excepts"])
    assert any("silent except-Exception" in f.message for f in found), \
        messages(found)
    assert not any("suppresses nothing" in f.message for f in found)


def test_stale_exemption_is_a_finding(tmp_path):
    (tmp_path / "m.py").write_text(
        "# lint: exempt[excepts] -- nothing here needs it\n"
        "x = 1\n")
    ctx = AnalysisContext.from_root(tmp_path, [tmp_path / "m.py"])
    found = core.run_passes(ctx, only=["excepts"])
    assert any("suppresses nothing" in f.message for f in found)
    # ...but only when the exempted pass actually ran: a --passes run
    # that skipped `excepts` must not misreport the exemption stale.
    assert core.run_passes(ctx, only=["envs"]) == []


# -- baseline (adoption) machinery -----------------------------------------

def test_baseline_absorbs_known_findings_but_not_new_ones(tmp_path):
    ctx = AnalysisContext.from_root(FIXTURES / "violating")
    found = core.run_passes(ctx, only=["excepts", "gates"])
    assert found
    bl_path = tmp_path / "baseline.json"
    core.write_baseline(bl_path, found)
    baseline = core.load_baseline(bl_path)
    assert core.new_findings(found, baseline) == []
    extra = core.Finding("gates", "x.py", 1, "brand new")
    assert core.new_findings(found + [extra], baseline) == [extra]


def test_cli_exit_codes_and_baseline_flow(tmp_path):
    env_cmd = [sys.executable, "-m", "onix.analysis",
               "--root", str(FIXTURES / "violating")]
    proc = subprocess.run(env_cmd, capture_output=True, text=True,
                          cwd=str(REPO))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    bl = tmp_path / "bl.json"
    proc = subprocess.run(env_cmd + ["--write-baseline", str(bl)],
                          capture_output=True, text=True, cwd=str(REPO))
    assert proc.returncode == 0
    assert json.loads(bl.read_text())["findings"]
    proc = subprocess.run(env_cmd + ["--baseline", str(bl)],
                          capture_output=True, text=True, cwd=str(REPO))
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- the real tree: the acceptance bar -------------------------------------

def test_repo_is_lint_clean_with_empty_baseline():
    """`python -m onix.analysis` over onix/, bench.py, and scripts/
    exits 0 with an EMPTY baseline: every finding either fixed or
    carrying an in-code exemption with justification. THE enforcement
    test — a regression in any of the eight contracts fails tier-1
    with the exact file:line and rule."""
    ctx = AnalysisContext.from_root(REPO)
    found = core.run_passes(ctx)
    assert found == [], "contract violations:\n" + messages(found)


def test_repo_scope_still_covers_the_r9_file_set():
    """The r9 lint's coverage contract, preserved across the move into
    onix/analysis: the serve/feedback/pallas-serve modules and the
    out-of-package harness files ride the default scope, so a package
    move can never silently drop them."""
    rels = {f.rel for f in AnalysisContext.from_root(REPO).files}
    for must in ("onix/serving/model_bank.py", "onix/feedback/filter.py",
                 "onix/models/pallas_serve.py", "onix/oa/serve.py",
                 "chip_smoke.py"):
        assert must in rels, f"analysis scope lost {must}"
    assert any(r.startswith("scripts/") for r in rels)


def test_fingerprint_contract_tables_are_coherent():
    """The declared fingerprint contract stays anchored to reality:
    every _SAMPLING_FIELDS member is in FINGERPRINT_FIELDS, the two
    tables are disjoint, and every entry names a real LDAConfig
    field — a renamed knob cannot leave a ghost declaration behind."""
    from onix import checkpoint
    from onix.config import LDAConfig
    import dataclasses

    fields = {f.name for f in dataclasses.fields(LDAConfig)}
    declared = set(checkpoint.FINGERPRINT_FIELDS)
    exempt = set(checkpoint.FINGERPRINT_EXEMPT)
    assert set(checkpoint._SAMPLING_FIELDS) <= declared
    assert not (declared & exempt)
    assert declared <= fields
    assert exempt <= fields


def test_lint_status_stamp():
    from onix.analysis import lint_status
    status = lint_status(REPO)
    assert status == {"version": core.ANALYSIS_VERSION, "findings": 0}
