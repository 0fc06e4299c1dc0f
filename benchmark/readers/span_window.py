"""The program's own closed spans of set-up: those that closed before
the window opened (`telemetry.TRACER.spans()`: each keeps its open
time `t0` on `time.monotonic()`, the clock of `run["t_start"]` and of
the drivers' windows; the window opens at `run["t_start"] +
run["end_to_end"]["setup_s"]`).

`spec["span"]` names the spans; the reader sums their `dur_s`, or the
attribute `spec["attr"]` names in its place; `spec["where"]`
(attribute -> value) keeps only the spans that carry those values. No
span of that name in set-up gives nothing; spans there of which none
passes `where` give 0 (a set-up that compiled, all of it from the
cache, has 0 cold seconds). Where the store has dropped a span of this
process the reader gives nothing, as `program_span` does, not a sum of
what is left. A program without the tracer, or from before spans kept
their `t0` (it reads 0 there), gives nothing too."""


def read(run: dict, spec: dict):
    try:
        from onix.utils import telemetry
        from onix.utils.obs import counters
    except ImportError:
        return None
    spans = telemetry.TRACER.spans()
    if counters.get("telemetry.spans_recorded") > len(spans):
        return None
    setup_s = run.get("end_to_end", {}).get("setup_s")
    if setup_s is None:
        return None
    t_open = run["t_start"] + setup_s
    mine = [s for s in spans if s.name == spec["span"]
            and 0.0 < s.t0 and s.t0 + s.dur_s <= t_open]    # t0 0: pre-PR 37
    if not mine:
        return None
    where = spec.get("where", {})
    return float(sum(
        s.attrs.get(spec["attr"], 0.0) if "attr" in spec else s.dur_s
        for s in mine if all(s.attrs.get(k) == v for k, v in where.items())))
