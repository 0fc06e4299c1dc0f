"""The device's idle gaps in the traced window, named by the program's
own spans: every `telemetry.TRACER` span lies on the `/host:CPU` plane
of the `--trace 1` run's xplane as `onix.<name>`, on the clock the
device's ops are on.

The window runs from the `bench.trace_open` mark to the
`bench.trace_close` mark; a device plane's gaps are the parts of it
that none of its `XLA Ops` events covers (its `XLA Modules` events
where it has no op line), as `tracered.reduce_planes` takes them. Each
gap is booked to the shortest `onix.*` span that covers at least half
of it - the rule `tracered.name_gap` uses, with the program's names -
or to `unspanned`; seconds are averaged over the device planes, so
`flow-fit-4chip` reads one chip's. The booking starts where the first
`onix.*` span of the trace opens: a span that was open when the trace
began is in no trace (`flow-fit`'s driver starts the trace inside the
program's `fit.notify`), so before that point idle time that a span
names cannot be told from idle time that none does.

With `spec["span"]` the reader returns the idle seconds booked to the
spans of that name that lie whole inside the window, over their count
(`idle_dispatch_s_per_chunk`: the device's wait under one
`scan.dispatch`; a span that straddles a mark enters neither side);
with `"as": "unspanned_pct"` the share of the booked idle time that no
span names, in percent - where the device idled `MIN_IDLE_S` or more:
under that a share swings on one gap of half a millisecond and the
reader gives nothing. A trace without the marks or without a device
plane, a window with no idle time, a program that writes no `onix.*`
span into the trace (one from before PR 26) or none of the span asked
for, give nothing, never 0."""
from __future__ import annotations

import bisect

from benchmark import tracered

SPAN_PREFIX = "onix."
UNSPANNED = "unspanned"
MARKS = ("bench.trace_open", "bench.trace_close")


#: Idle seconds a device plane under which `unspanned_pct` gives
#: nothing: `flow-stream-catchup` idles 4 ms of 13.6 s, and its share
#: read 22.4% and 0.0% on two runs of one tree (PR 37).
MIN_IDLE_S = 0.005


def _namer(spans: list[tuple[str, int, int]]):
    """gap -> the index of the shortest span that covers at least half
    of it, else None. Between two neighbouring span boundaries the set
    of spans that cover a point does not change, and nearly every gap
    (the breath between two ops) lies whole between two: those are
    answered from a table of the shortest span over each such stretch,
    the few that cross a boundary by a pass over the spans."""
    points = sorted({t for _, s, e in spans for t in (s, e)})
    over = [min(((e - s, i) for i, (_, s, e) in enumerate(spans)
                 if s <= lo and e >= hi), default=(0, None))[1]
            for lo, hi in zip(points, points[1:])]

    def name(gap: tuple[int, int]) -> int | None:
        a, b = gap
        if not points or b <= points[0] or a >= points[-1]:
            return None
        i = bisect.bisect_right(points, a) - 1
        if i >= 0 and b <= points[i + 1]:
            return over[i]
        return min(((e - s, i) for i, (_, s, e) in enumerate(spans)
                    if 2 * (min(e, b) - max(s, a)) >= b - a),
                   default=(0, None))[1]
    return name


def book(planes: list[dict]) -> dict | None:
    """`planes` as `scope_seconds.read_planes` gives them (events as
    (name, start, duration, ...), any one unit). Returns {"idle":
    units of idle time a device plane, "by_span": {name or `UNSPANNED`:
    units booked to it}, "whole": {name: [spans of that name whole
    inside the window, units booked to those]}}, or None where there is
    nothing to read."""
    marks, spans = {}, []
    for p in planes:
        if not p["name"].startswith(tracered.HOST_PLANE):
            continue
        for line in p["lines"]:
            for name, s, d, *_ in line["events"]:
                if name in MARKS:
                    marks[name] = (s, s + d)
                elif name.startswith(SPAN_PREFIX):
                    spans.append((name[len(SPAN_PREFIX):], s, s + d))
    dev = [p for p in planes if p["name"].startswith(tracered.DEVICE_PLANE)]
    if len(marks) != 2 or not dev or not spans:
        return None
    lo, hi = marks[MARKS[0]][0], marks[MARKS[1]][1]
    is_whole = [s >= lo and e <= hi for _, s, e in spans]
    whole: dict[str, list] = {}
    for (n, _, _), inside in zip(spans, is_whole):
        if inside:
            whole.setdefault(n, [0, 0.0])[0] += 1
    seen_from = max(lo, min(s for _, s, _ in spans))
    span_of = _namer(spans)
    by_span: dict[str, float] = {}
    for p in dev:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        events = lines.get(tracered.OP_LINE) or lines.get(
            tracered.MODULE_LINE, [])
        busy = [(max(s, seen_from), min(s + d, hi)) for _, s, d, *_ in events
                if min(s + d, hi) > max(s, seen_from)]
        for gap in tracered.gaps(busy, seen_from, hi):
            i, share = span_of(gap), (gap[1] - gap[0]) / len(dev)
            k = UNSPANNED if i is None else spans[i][0]
            by_span[k] = by_span.get(k, 0.0) + share
            if i is not None and is_whole[i]:
                whole[k][1] += share
    return {"idle": sum(by_span.values()), "by_span": by_span,
            "whole": whole}


def read(run: dict, spec: dict):
    if "scope_planes" not in run:       # one parse for the cell's metrics
        reader = run["manifest"].load("readers", "scope_seconds")
        try:
            run["scope_planes"] = reader.read_planes(
                tracered.find_xplane(run["tracer"].dir))
        except FileNotFoundError:
            run["scope_planes"] = []
    if "idle_under_span" not in run:
        run["idle_under_span"] = book(run["scope_planes"])
    booked = run["idle_under_span"]
    if booked is None or booked["idle"] <= 0:
        return None
    # scope_seconds.read_planes keeps the file's picoseconds.
    if spec.get("as") == "unspanned_pct":
        if booked["idle"] * 1e-12 < MIN_IDLE_S:
            return None
        return 100.0 * booked["by_span"].get(UNSPANNED, 0.0) / booked["idle"]
    n, idle = booked["whole"].get(spec["span"], (0, 0.0))
    return idle * 1e-12 / n if n else None
