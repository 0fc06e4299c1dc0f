"""Reduction of one profiler trace (`*.xplane.pb`) to the numbers the
per-layer readers use. Kept with the benchmark so that every PR computes
the same number the same way.

What a TPU trace holds (jax 0.9, libtpu 0.0.34; looked at by hand in
PR 25): one plane per chip named `/device:TPU:<n>` with the lines
`XLA Modules` (one event per executed program), `XLA Ops` (one event per
executed HLO op) and `Steps`; and a host plane `/host:CPU` with one line
per thread, on which `jax.profiler.TraceAnnotation` spans appear under
their own names. All lines share one clock.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return files[-1]


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals (any unit)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The parts of [lo, hi) that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def self_seconds(events: list[tuple[str, float, float]]) -> dict[str, float]:
    """Per name, the time of its events less what events nested in them
    cover (a `while` op spans the ops of its body: its self time is what
    is left). Events of one line nest or lie apart; (name, start, end)."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [name, end, self]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, _, own = stack.pop()
            out[n] = out.get(n, 0.0) + own
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    for n, _, own in stack:
        out[n] = out.get(n, 0.0) + own
    return out


def reduce_planes(planes: list[dict], window: tuple[float, float] | None = None
                  ) -> dict:
    """`planes`: [{name, lines: [{name, events: [(name, start_ns, dur_ns)]}]}].

    Returns busy_s (union of device-op intervals, averaged over the device
    planes), window_s, per-module device seconds and counts (`whole`: the
    executions that lie whole inside the window, `whole_seconds` theirs),
    per-op self seconds,
    and the longest idle gaps named by the `bench.*` host span that covers
    at least half of each (the shortest such span). `window` (ns) clips
    everything; by default it runs from the `bench.trace_open` mark to the
    `bench.trace_close` mark that `harness.Tracer` writes."""
    dev = [p for p in planes if p["name"].startswith(DEVICE_PLANE)]
    host_spans = []
    for p in planes:
        if p["name"].startswith(HOST_PLANE):
            for line in p["lines"]:
                host_spans += [(n[len(SPAN_PREFIX):], s, s + d)
                               for n, s, d in line["events"]
                               if n.startswith(SPAN_PREFIX)]
    marks = {n: (s, e) for n, s, e in host_spans
             if n in ("trace_open", "trace_close")}
    host_spans = [h for h in host_spans if h[0] not in marks]
    if window is None:
        if len(marks) != 2:
            raise ValueError("trace lacks the bench.trace_open and "
                             "bench.trace_close marks")
        window = (marks["trace_open"][0], marks["trace_close"][1])
    lo, hi = window
    modules: dict[str, list[float]] = {}
    ops: dict[str, float] = {}
    busy, all_gaps = [], []
    for p in dev:
        iv, named = [], []
        for line in p["lines"]:
            if line["name"] not in (MODULE_LINE, OP_LINE):
                continue
            for name, s, d in line["events"]:
                s2, e2 = max(s, lo), min(s + d, hi)
                if e2 <= s2:
                    continue
                if line["name"] == MODULE_LINE:
                    whole = s >= lo and s + d <= hi
                    m = modules.setdefault(name, [0.0, 0, 0, 0.0])
                    m[0] += (e2 - s2) * 1e-9
                    m[1] += 1
                    m[2] += int(whole)
                    m[3] += d * 1e-9 * whole
                else:
                    iv.append((s2, e2))
                    named.append((name, s2, e2))
        for name, own in self_seconds(named).items():
            ops[name] = ops.get(name, 0.0) + own * 1e-9
        if not iv:      # a plane with modules but no op line
            iv = [(max(s, lo), min(s + d, hi)) for line in p["lines"]
                  if line["name"] == MODULE_LINE
                  for _, s, d in line["events"] if min(s + d, hi) > max(s, lo)]
        busy.append(union_seconds(iv) * 1e-9)
        all_gaps += gaps(iv, lo, hi)
    if not dev:
        raise ValueError("trace holds no device plane")

    def name_gap(g):
        """The shortest host span that covers at least half the gap."""
        best, length = "unattributed", None
        for n, s, e in host_spans:
            if (min(e, g[1]) - max(s, g[0]) >= 0.5 * (g[1] - g[0])
                    and (length is None or e - s < length)):
                best, length = n, e - s
        return best

    by_phase: dict[str, float] = {}
    for g in all_gaps:
        k = name_gap(g)
        by_phase[k] = by_phase.get(k, 0.0) + (g[1] - g[0]) * 1e-9 / len(dev)
    spans: dict[str, list[float]] = {}
    for n, s, e in host_spans:
        spans.setdefault(n, []).append((e - s) * 1e-9)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (hi - lo) * 1e-9,
        "n_devices": len(dev),
        # Per device, averaged: an SPMD step is one execution on each chip.
        "modules": {k: {"seconds": v[0] / len(dev), "count": v[1] / len(dev),
                        "whole": v[2] / len(dev),
                        "whole_seconds": v[3] / len(dev)}
                    for k, v in modules.items()},
        "device_ops": sorted(([k, v / len(dev)] for k, v in ops.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v] for k, v in by_phase.items()),
                            key=lambda kv: -kv[1]),
        "host_spans": spans,
    }


def read_planes(path: str) -> list[dict]:
    """An xplane file as plain lists (needs only jax)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        keep = (plane.name.startswith(DEVICE_PLANE)
                or plane.name.startswith(HOST_PLANE))
        if not keep:
            continue
        lines = []
        for line in plane.lines:
            if plane.name.startswith(HOST_PLANE):
                ev = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
            elif line.name in (MODULE_LINE, OP_LINE):
                ev = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events]
            else:
                continue
            if ev:
                lines.append({"name": line.name, "events": ev})
        out.append({"name": plane.name, "lines": lines})
    return out


def reduce_trace(trace_dir: str) -> dict:
    return reduce_planes(read_planes(find_xplane(trace_dir)))
