"""Seconds of the program's own spans inside the timed window, over
the window's count of `spec["per"]` (a key of `run["window"]`).

`telemetry.TRACER.spans()` keeps every closed span with its open time
`t0` on `time.monotonic()`, the clock of `run["t_start"]` and of the
drivers' windows: the window opens at `run["t_start"] +
run["end_to_end"]["setup_s"]` and lasts `run["window"]["elapsed_s"]`.
The reader sums `dur_s` over the spans named in `spec["spans"]` that
lie whole inside it, less that of the spans named in `spec["less"]`
(children counted by another metric) that lie whole inside it. Spans of those names in the run but none in the
window give 0; a program that never opened one (from before they
were written), a store that has dropped a span of this process, or a
driver that states no window give nothing."""


def read(run: dict, spec: dict):
    try:
        from onix.utils import telemetry
        from onix.utils.obs import counters
    except ImportError:
        return None
    spans = telemetry.TRACER.spans()
    if counters.get("telemetry.spans_recorded") > len(spans):
        return None
    window = run.get("window", {})
    setup_s = run.get("end_to_end", {}).get("setup_s")
    per = window.get(spec["per"])
    if setup_s is None or "elapsed_s" not in window or not per:
        return None
    names, less = set(spec["spans"]), set(spec.get("less", ()))
    mine = [s for s in spans if s.name in names | less and s.t0 > 0.0]
    if not any(s.name in names for s in mine):
        return None
    t_open = run["t_start"] + setup_s
    t_close = t_open + window["elapsed_s"]
    return sum((-1.0 if s.name in less else 1.0) * s.dur_s for s in mine
               if s.t0 >= t_open and s.t0 + s.dur_s <= t_close) / per
