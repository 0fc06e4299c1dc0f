"""Device seconds by `onix.*` scope (`jax.named_scope` inside the
program's jitted code), from the traced run's xplane.

The window runs from the `bench.trace_open` mark to the
`bench.trace_close` mark; the executions counted are those of the
programs whose name holds `spec["module_match"]` that lie whole inside
it; each `XLA Ops` event inside one of them gives its self time
(`tracered.self_seconds`, keyed by scope, so a `while` gives up its
body) to the innermost `onix.*` part of its op name, else to
`unscoped`. With `spec["scope"]` (a prefix) the reader returns the
seconds under that scope per whole execution; with `"as":
"unscoped_pct"` the unscoped share of those programs' op time in
percent. A trace without the marks, without such an execution, or with
no scope at all (a program from before the scopes) gives nothing,
never 0.

Where the op name is (jax 0.9.0, libtpu 0.0.34; looked at by hand in
PR 26): not among the stats of the event, which are all that
`jax.profiler.ProfileData` hands out (`device_offset_ps`,
`device_duration_ps`, `Time Scale Multiplier`), but in the stat `tf_op`
of the event's *metadata* (`XEventMetadata.stats`), as
`jit(_dns_stream_scan)/while/body/closed_call/onix.words.lookup_doc/
jit(searchsorted)/vmap()/while/body/closed_call/gather:`. A fusion
carries its root's. So this file reads the few fields it needs from
the protobuf's wire format itself; nothing but the standard library.
"""
from __future__ import annotations

import bisect

from benchmark import tracered

SCOPE_PREFIX = "onix."
UNSCOPED = "unscoped"
OP_NAME_STAT = "tf_op"
MARKS = ("bench.trace_open", "bench.trace_close")


def _varint(buf, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed
    32- and 64-bit fields are passed over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, wire, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, wire, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key, value = 0, b""
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf, want_line) -> dict:
    """One XPlane: {name, lines: [{name, events: [(name, start_ps,
    dur_ps, op_name)]}]} for the lines `want_line(name)` keeps. Whole
    picoseconds, as the file has them: neighbours that touch stay
    neighbours, where rounded nanoseconds would nest one in the other
    and `self_seconds` would count the second twice."""
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.update([_map_entry(v)])
        elif f == 5:
            k, meta = _map_entry(v)
            stat_names[k] = next(
                (_text(x) for g, _, x in _fields(meta) if g == 2), "")
    op_stat = {k for k, n in stat_names.items() if n == OP_NAME_STAT}
    meta: dict[int, tuple[str, str]] = {}

    def event_meta(mid):
        if mid not in meta:
            ev_name, op_name = "", ""
            for f, _, v in _fields(ev_meta.get(mid, b"")):
                if f == 2:
                    ev_name = _text(v)
                elif f == 5:
                    stat = dict((g, x) for g, _, x in _fields(v))
                    if stat.get(1) in op_stat:
                        op_name = (_text(stat[5]) if 5 in stat else
                                   stat_names.get(stat.get(7), ""))
            meta[mid] = (ev_name, op_name)
        return meta[mid]

    out = []
    for line in lines:
        lname, t0, events = "", 0, []
        for f, _, v in _fields(line):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        if not want_line(lname):
            continue
        evs = []
        for ev in events:
            mid = off = dur = 0
            for f, _, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            ev_name, op_name = event_meta(mid)
            evs.append((ev_name, t0 * 1000 + off, dur, op_name))
        out.append({"name": lname, "events": evs})
    return {"name": name, "lines": out}


def read_planes(path: str) -> list[dict]:
    """The device planes' `XLA Modules` and `XLA Ops` lines and every
    line of the host planes, events as (name, start_ps, dur_ps,
    op_name)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for f, _, v in _fields(space):
        if f != 1:
            continue
        pname = next((_text(x) for g, _, x in _fields(v) if g == 2), "")
        if pname.startswith(tracered.DEVICE_PLANE):
            out.append(_plane(v, lambda n: n in (tracered.MODULE_LINE,
                                                 tracered.OP_LINE)))
        elif pname.startswith(tracered.HOST_PLANE):
            out.append(_plane(v, lambda n: True))
    return out


def scope_of(op_name: str) -> str:
    """The innermost `onix.*` part of an op name, else `unscoped`."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part.rstrip(":")
    return UNSCOPED


def book(planes: list[dict], module_match: str):
    """(seconds by scope, whole executions), each averaged over the
    device planes; None where there is nothing to read."""
    marks = {}
    for p in planes:
        if p["name"].startswith(tracered.HOST_PLANE):
            for line in p["lines"]:
                for name, s, d, *_ in line["events"]:
                    if name in MARKS:
                        marks[name] = (s, s + d)
    if len(marks) != 2:
        return None
    lo, hi = marks[MARKS[0]][0], marks[MARKS[1]][1]
    by_scope: dict[str, float] = {}
    whole, devices = 0, 0
    for p in planes:
        if not p["name"].startswith(tracered.DEVICE_PLANE):
            continue
        devices += 1
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        runs = sorted((s, s + d) for name, s, d, *_ in
                      lines.get(tracered.MODULE_LINE, [])
                      if module_match in name and s >= lo and s + d <= hi)
        whole += len(runs)
        starts = [r[0] for r in runs]
        inside = []
        for _, s, d, op_name in lines.get(tracered.OP_LINE, []):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s + d <= runs[i][1]:
                inside.append((scope_of(op_name), s, s + d))
        for scope, own in tracered.self_seconds(inside).items():
            by_scope[scope] = by_scope.get(scope, 0.0) + own * 1e-12
    if not whole or set(by_scope) <= {UNSCOPED}:
        return None
    return ({k: v / devices for k, v in by_scope.items()}, whole / devices)


def read(run: dict, spec: dict):
    if "scope_planes" not in run:       # one parse for the cell's metrics
        try:
            run["scope_planes"] = read_planes(
                tracered.find_xplane(run["tracer"].dir))
        except FileNotFoundError:
            run["scope_planes"] = []
    booked = book(run["scope_planes"], spec["module_match"])
    if booked is None:
        return None
    by_scope, whole = booked
    if spec.get("as") == "unscoped_pct":
        return 100.0 * by_scope.get(UNSCOPED, 0.0) / sum(by_scope.values())
    seconds = sum(v for k, v in by_scope.items()
                  if k.startswith(spec["scope"]))
    return seconds / whole if seconds > 0 else None
