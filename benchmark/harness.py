"""What every cell shares: the manifest, the look-up of a cell's files by
name, the look for the chip, the compile cache and the count of
compilations, host spans, the trace, and the result line.

Nothing here knows a configuration, a traffic mix or a metric: those are
files (see README.md), found by the names that `BENCHMARK.json` gives.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
KINDS = {"traffic": ".json", "metrics": ".json", "drivers": ".py",
         "readers": ".py"}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class Manifest:
    """`BENCHMARK.json` and the directories it lists in `paths`.

    A later PR may add a directory of its own to `paths`; every kind of
    file (`traffic/`, `metrics/`, `drivers/`, `readers/`) is looked up in
    each of them, and last beside this file; a configuration's file is
    where its entry says."""

    def __init__(self, path: pathlib.Path | None = None):
        self.path = pathlib.Path(path or ROOT / "BENCHMARK.json").resolve()
        self.root = self.path.parent
        self.data = json.loads(self.path.read_text())
        self.dirs = [self.root / p for p in self.data["paths"]]
        if HERE not in self.dirs:
            self.dirs.append(HERE)

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}")

    def find(self, kind: str, name: str) -> pathlib.Path:
        for d in self.dirs:
            p = d / kind / (name + KINDS[kind])
            if p.is_file():
                return p
        raise FileNotFoundError(
            f"{kind}/{name}{KINDS[kind]} is in none of "
            f"{[str(d) for d in self.dirs]}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in {self.path}")

    def traffic(self, name: str) -> dict:
        return json.loads(self.find("traffic", name).read_text())

    def load(self, kind: str, name: str):
        """Import `<kind>/<name>.py` as a module of its own."""
        path = self.find(kind, name)
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics_of(self, cell: str, section: str) -> list[dict]:
        """The cell's metrics of `end_to_end` or `per_layer`. A metric
        without `workloads` belongs to every cell that reports what it
        `moves` (per-layer) or to every cell (end-to-end)."""
        e2e = {m["name"]: m for m in self.data["end_to_end"]}
        mine = {m["name"] for m in e2e.values()
                if cell in m.get("workloads", [cell])}
        if section == "end_to_end":
            return [e2e[n] for n in e2e if n in mine]
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def fold_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; the program's PRNG keys and numpy
    seeds take an int32. One value in, one value out."""
    return int(seed) % (2 ** 31 - 1)


def set_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed place inside the
    checkout, or where JAX_COMPILATION_CACHE_DIR says (JAX reads that
    itself). Every program is cached, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def look_for_chip(chips: int) -> dict:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX reports "
                     f"{len(devices)} x {devices[0].platform}")
    return describe_device()


def describe_device() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peak_bytes() -> int:
    """`peak_bytes_in_use` of the fullest chip: a peak over the life of
    the process, so read it before the reference touches the device."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts the compilations JAX starts (cache hits and misses alike:
    either way a program was not ready), as `chip_smoke.py` does."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_):
        if event == self.EVENT:
            self.n += 1


class Spans:
    """Host spans from the benchmark's own files: kept in memory under
    their names, and written into the profiler's trace as `bench.<name>`
    so that idle gaps on the device can be named."""

    def __init__(self):
        self.spans: dict[str, list[tuple[float, float]]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax.profiler
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench." + name):
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append((t0, time.monotonic()))

    def add(self, name: str, t0: float, t1: float):
        self.spans.setdefault(name, []).append((t0, t1))


class Tracer:
    """Traces the first part of the window of a `--trace 1` run. The
    trace directory is fixed inside the checkout and emptied first."""

    def __init__(self, on: bool, cell: str):
        self.on = on
        self.dir = str(ROOT / ".bench_trace" / cell)
        self.running = False
        self.done = False

    def start(self):
        if self.on and not self.done and not self.running:
            import shutil

            import jax.profiler
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            with jax.profiler.TraceAnnotation("bench.trace_open"):
                pass
            self.running = True

    def stop(self):
        if self.running:
            import jax.profiler
            with jax.profiler.TraceAnnotation("bench.trace_close"):
                pass
            jax.profiler.stop_trace()
            self.running, self.done = False, True


class Check:
    """The numbers compared, each beside its limit. `correct` is that
    every one of them is at or under its limit."""

    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []
        self.notes: dict[str, float] = {}

    def compare(self, name: str, value: float, limit: float):
        self.rows.append((name, float(value), float(limit)))

    def note(self, name: str, value: float):
        """A reading shown beside the compared ones, with no limit."""
        self.notes[name] = float(value)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            v == v and v <= lim for _, v, lim in self.rows)

    def as_dict(self) -> dict:
        out = {n: {"value": v, "limit": lim} for n, v, lim in self.rows}
        out.update({n: {"value": v} for n, v in self.notes.items()})
        return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             manifest: Manifest | None = None, require_chip: bool = True,
             control: str | None = None, t_start: float | None = None
             ) -> dict:
    """One run of one cell. Returns the result line as a dict."""
    t_start = time.monotonic() if t_start is None else t_start
    mf = manifest or Manifest()
    cell = mf.cell(workload)
    config = mf.config(cell["config"])
    traffic = mf.traffic(cell["traffic"])
    set_compile_cache()
    device = look_for_chip(cell["chips"]) if require_chip else describe_device()
    driver = mf.load("drivers", traffic["driver"])
    spans, compiles, check = Spans(), CompileCounter(), Check()
    tracer = Tracer(trace, workload)
    run = {
        "cell": cell, "config": config, "traffic": traffic,
        "seed": int(seed), "seconds": float(seconds), "trace": bool(trace),
        "t_start": t_start, "spans": spans, "compiles": compiles,
        "tracer": tracer, "check": check, "control": control,
        "manifest": mf, "device": device,
    }
    try:
        out = driver.run(run)
    finally:
        tracer.stop()
    run.update(out)
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    check.compare("compiles_in_window", out["compiles_in_window"], 0)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    if trace:
        from benchmark import models, tracered
        red = tracered.reduce_trace(tracer.dir)
        run["trace_summary"] = red
        run["peaks"] = (models.load_peaks(device["kind"])
                        if device["platform"] == "tpu" else None)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    for m in mf.metrics_of(workload, section):
        if not trace:
            value = out["end_to_end"].get(m["name"])
        else:
            spec = json.loads(mf.find("metrics", m["name"]).read_text())
            value = mf.load("readers", spec["reader"]).read(run, spec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {
        "correct": check.correct,
        "attempted": int(out["attempted"]), "failed": int(out["failed"]),
        "metrics": metrics, "device": device,
    }
    if trace:
        line["breakdown"] = {
            "device_ops": [[n[:80], s] for n, s in red["device_ops"][:10]],
            "idle_gaps": [[n, s] for n, s in red["idle_gaps"][:10]]}
    line["window"] = dict(out.get("window", {}), spans={
        k: [len(v), sum(b - a for a, b in v)] for k, v in spans.spans.items()})
    if out.get("controls"):
        line["controls"] = out["controls"]
    line["check"] = check.as_dict()
    return line


def print_result(line: dict) -> None:
    """The compared numbers as the last lines of standard error, then the
    one result line as the last line of standard output."""
    sys.stdout.flush()
    for name, row in line["check"].items():
        lim = f" limit {row['limit']:.6g}" if "limit" in row else ""
        print(f"check {name} {row['value']:.6g}{lim}", file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
