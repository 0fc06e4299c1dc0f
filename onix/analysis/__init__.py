"""Contract linter — registry-driven multi-pass static analysis.

Eleven PRs grew onix around a handful of load-bearing conventions:
form gates resolve through `config.resolve_form_gate`, semantics-
changing knobs join checkpoint fingerprints (the r11/r14 resume-refusal
contract), every `ONIX_*` env and `faults.fire` site is documented,
counters land in a declared namespace, and no exception is swallowed
silently. Until r17 only ONE of those conventions (the r9
except-swallow rule) was machine-checked, as a single test buried in
tests/test_faults.py. Staleness- and parallelism-heavy designs like
AD-LDA (arxiv 0909.4603) and streaming Gibbs (arxiv 1601.01142) are
exactly the kind where a knob that silently misses the fingerprint or
a shared field mutated off-lock produces wrong-but-plausible results —
so every discipline is now a PASS over the AST, run by tier-1
(tests/test_analysis.py) and by `python -m onix.analysis` /
`onix-lint` (scripts/lint.sh bundles the native sanitizer test).

Passes (onix/analysis/passes.py; each has a fixture test proving it
fires on a violation and stays silent on the fixed form):

  excepts       bare/broad except handlers must log, count, or re-raise
  envs          literal ONIX_* env reads must be declared in
                config.ENV_REGISTRY; dead declarations flagged
  counters      literal counter keys / f-string prefixes must open with
                a namespace declared in obs.COUNTER_NAMESPACES
  spans         literal span names opened on the tracer must be
                declared in telemetry.SPAN_REGISTRY (r18); dead
                declarations and non-literal names flagged
  gates         select_*_form gates and _*_MIN_* crossover tables must
                resolve through config.resolve_form_gate
  fingerprints  LDAConfig fields read inside the engine modules must be
                fingerprint-contributing (checkpoint.FINGERPRINT_FIELDS)
                or exempt with a justification
  tracehaz      host nondeterminism / implicit device syncs inside
                functions reachable from jit/pallas_call/scan bodies
  locks         GUARDED_BY-declared attributes of threaded classes may
                only be mutated under their declared lock
  faultdocs     faults.fire sites <-> the ROBUSTNESS.md site table, and
                the generated registry tables must be current

Exemption mechanism: `# lint: exempt[pass-id] -- justification` on the
finding's line (or the line above); `# lint: holds[lock]` on a `def`
line asserts the method's callers hold the lock. Exemptions without a
justification, and exemptions that suppress nothing, are themselves
findings — the escape hatch cannot rot into a blanket mute.
"""

from onix.analysis.core import (  # noqa: F401
    ANALYSIS_VERSION,
    AnalysisContext,
    Finding,
    default_targets,
    load_baseline,
    new_findings,
    run_passes,
)


def lint_status(root=None) -> dict:
    """One-call summary for artifact stamping: the analyzer version
    and the finding count over the default scope. A lint-clean tree
    stamps {"version": N, "findings": 0}."""
    ctx = AnalysisContext.from_root(root)
    found = run_passes(ctx)
    return {"version": ANALYSIS_VERSION, "findings": len(found)}
