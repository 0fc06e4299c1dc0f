"""Seconds of the program's own closed spans, read from memory
(`telemetry.TRACER.spans()`: the flight ring's exact `dur_s`, not the
log buckets of `/metrics`). `spec["span"]` with `"stat": "sum"` or
`"median"` over the run's spans of that name; or with `"children_of":
<parent span>` the median, over the parents that have any, of the
summed duration of their children of that name. The ring is bounded
(spans and counter deltas share it): where it has dropped a span of
this process the reader gives nothing, not a sum of what is left. A
program without the span, or without the tracer, gives nothing too."""
import statistics


def read(run: dict, spec: dict):
    try:
        from onix.utils import telemetry
        from onix.utils.obs import counters
    except ImportError:
        return None
    spans = telemetry.TRACER.spans()
    if counters.get("telemetry.spans_recorded") > len(spans):
        return None
    mine = [s for s in spans if s.name == spec["span"]]
    parent = spec.get("children_of")
    if parent:
        parents = {s.span_id for s in spans if s.name == parent}
        per: dict[int, float] = {}
        for s in mine:
            if s.parent_id in parents:
                per[s.parent_id] = per.get(s.parent_id, 0.0) + s.dur_s
        return statistics.median(per.values()) if per else None
    if not mine:
        return None
    durs = [s.dur_s for s in mine]
    return sum(durs) if spec["stat"] == "sum" else statistics.median(durs)
