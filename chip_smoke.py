"""chip_smoke.py — the quickest proof that onix still starts on the chip.

Drives the product path ONCE, through the entry points a user calls, in
ONE process that owns every visible chip, at the judged width (K=20,
vocabularies from the real word builders, LDAConfig's own sweeps and
block size for the CLI day, run_scale's own for the scale path; only
event counts are cut):

  phase 0  device      what JAX reports; not a TPU -> exit 2, nothing run
  phase 1  cli day     `onix demo`: synth -> store -> words -> corpus ->
                       GibbsLDA.fit -> score -> results CSV -> OA, for
                       flow, dns and proxy (BASELINE configs 1-3)
  phase 2  server      `onix.oa.serve.make_server` over the models phase 1
                       persisted: POST /score per tenant over real HTTP,
                       winners bit-identical to scoring.top_suspicious
  phase 3  scale path  `run_scale` (BASELINE config 4's code, 1/100 length)

Every number it prints is a SMOKE OBSERVATION, NOT A BENCHMARK: one cold
run, compile included. Not driven here: `onix stream` (its resident
superstep runs on the chip in the benchmark's `flow-stream-catchup`
cell since PR 35), `pipelines.daily`, `pipelines.fleet`, the host
fabric.

Exit 0 only if every phase passed; then the last stdout line is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Any failure exits non-zero, names its phase on stderr and prints no
result line.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import threading
import time
import traceback
import urllib.request

import numpy as np
import pandas as pd

DEMO_EVENTS = 1_000_000         # per datatype, phase 1
REQUEST_EVENTS = 4096           # per /score body, phase 2
REQUEST_MAX_RESULTS = 1000
SCALE_EVENTS = 10_000_000       # phase 3: docs/SCALE_1E7_r06_flow.json's
SCALE_TRAIN_EVENTS = 1_000_000  # shape (CPU run: 737 of 1000 planted)
SCALE_MIN_PLANTED = 600


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, message: str) -> None:
    """`assert` that survives -O."""
    if not cond:
        raise SmokeFailure(message)


class CacheCounter:
    """Persistent compile-cache traffic, from JAX's own monitoring
    events: `requests` programs asked the cache, `hits` were found
    there, `misses` were compiled and written. A warm second run
    reports zero misses."""

    _EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
               "requests",
               "/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self._counts = dict.fromkeys(self._EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        name = self._EVENTS.get(event)
        if name is not None:
            with self._lock:
                self._counts[name] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)


def phase0_device(require_tpu: bool = True) -> dict:
    """What JAX reports. On anything but a TPU whose kind the roofline
    table knows, and whose Pallas kernels compile, the smoke stops."""
    import jax

    from onix.models.pallas_serve import pallas_mode
    from onix.utils.obs import (device_peak_bytes_per_s, device_summary,
                                enable_compile_cache)

    device = device_summary()
    print(f"chip_smoke: jax {jax.__version__}, platform "
          f"{device['platform']}, device_kind {device['kind']!r}, "
          f"{device['count']} device(s)", flush=True)
    if require_tpu and device["platform"] != "tpu":
        print("chip_smoke: no TPU — JAX found no accelerator (platform "
              f"{device['platform']!r}). This script proves the program "
              "runs on the chip; it does not run here.", file=sys.stderr)
        raise SystemExit(2)
    peak, source = device_peak_bytes_per_s()    # unknown kind raises
    mode = pallas_mode()
    print(f"chip_smoke: HBM peak {peak:.3g} B/s ({source}); "
          f"pallas_mode {mode}", flush=True)
    if require_tpu:
        check(mode == "compiled",
              f"pallas_mode is {mode!r} on a TPU (ONIX_PALLAS_INTERPRET "
              "set?) — the kernels must compile here")
    enable_compile_cache()
    return {"jax": jax.__version__, "device": device,
            "hbm_peak_bytes_per_s": peak, "pallas_mode": mode,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir}


def _demo_overrides(work: pathlib.Path) -> list[str]:
    return [f"store.root={work / 'store'}", "serving.save_fitted=true"]


def phase1_cli_day(work: pathlib.Path,
                   n_events: int = DEMO_EVENTS) -> dict:
    """BASELINE configs 1-3 through the operator's own command."""
    from onix import cli
    from onix.config import DATATYPES, load_config
    from onix.setup_cmd import DEMO_DATE
    from onix.store import results_path

    argv = ["demo", "--events", str(n_events)]
    for override in _demo_overrides(work):
        argv += ["-s", override]
    rc = cli.main(argv)
    check(rc == 0, f"`onix {' '.join(argv)}` returned {rc}")

    cfg = load_config(None, _demo_overrides(work))
    out = {}
    for datatype in DATATYPES:
        csv = results_path(cfg.store.results_dir, datatype, DEMO_DATE)
        check(csv.exists(), f"no results file {csv}")
        manifest = json.loads(
            csv.with_suffix(".manifest.json").read_text())
        scores = pd.read_csv(csv, usecols=["score"])["score"].to_numpy()
        check(len(scores) > 0 and len(scores) == manifest["n_results"],
              f"{datatype}: {len(scores)} result rows, manifest says "
              f"{manifest['n_results']}")
        check(bool(np.isfinite(scores).all()),
              f"{datatype}: non-finite winner scores")
        ll = [v for _, v in manifest["ll_history"]]
        check(all(np.isfinite(ll)) and ll[-1] > ll[0],
              f"{datatype}: log-likelihood did not rise over the fit: {ll}")
        out[datatype] = {
            "n_events": manifest["n_events"],
            "n_docs": manifest["n_docs"], "n_vocab": manifest["n_vocab"],
            "n_tokens": manifest["n_tokens"],
            "n_results": manifest["n_results"],
            "ll_first": ll[0], "ll_last": ll[-1],
            "score_min": float(scores.min()),
            "score_max": float(scores.max()),
            "wall_seconds": manifest["wall_seconds"]}
    return out


def _http_json(url: str, body: dict | None = None) -> dict:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        check(resp.status == 200, f"{url}: HTTP {resp.status}")
        return json.loads(resp.read())


def phase2_serve(work: pathlib.Path,
                 n_events: int = REQUEST_EVENTS,
                 max_results: int = REQUEST_MAX_RESULTS) -> dict:
    """The server answers, from the models phase 1 persisted, what the
    single-tenant scan answers on the same device."""
    import jax.numpy as jnp

    from onix.checkpoint import load_model
    from onix.config import DATATYPES, load_config
    from onix.models.scoring import top_suspicious
    from onix.oa.serve import make_server
    from onix.setup_cmd import DEMO_DATE
    from onix.store import model_name

    cfg = load_config(None, _demo_overrides(work))
    server = make_server(cfg, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    out = {}
    try:
        rng = np.random.default_rng(21)
        first_body = None
        for datatype in DATATYPES:
            tenant = model_name(datatype, DEMO_DATE)
            model = load_model(cfg.serving.models_dir, tenant)
            theta, phi = model.arrays["theta"], model.arrays["phi_wk"]
            d = rng.integers(0, theta.shape[0], n_events).astype(np.int32)
            w = rng.integers(0, phi.shape[0], n_events).astype(np.int32)
            body = {"requests": [{"tenant": tenant, "window": "w0",
                                  "doc_ids": d.tolist(),
                                  "word_ids": w.tolist()}],
                    "tol": 1.0, "max_results": max_results}
            first_body = first_body or body
            (res,) = _http_json(base + "/score", body)["results"]
            ref = top_suspicious(
                jnp.asarray(theta), jnp.asarray(phi), jnp.asarray(d),
                jnp.asarray(w), jnp.ones(n_events, jnp.float32),
                tol=1.0, max_results=max_results)
            got_s = np.asarray([np.inf if s is None else s
                                for s in res["scores"]], np.float32)
            check(res["indices"] == np.asarray(ref.indices).tolist()
                  and np.array_equal(got_s, np.asarray(ref.scores)),
                  f"{tenant}: /score winners differ from top_suspicious")
            check(bool(np.isfinite(got_s).all()),
                  f"{tenant}: non-finite winners")
            check(res["cached"] is False and res["degraded"] is False,
                  f"{tenant}: cached={res['cached']} "
                  f"degraded={res['degraded']}")
            out[tenant] = {"theta": list(theta.shape),
                           "phi_wk": list(phi.shape),
                           "n_winners": len(res["indices"])}
        (again,) = _http_json(base + "/score", first_body)["results"]
        check(again["cached"] is True and again["degraded"] is False,
              f"repeated window: cached={again['cached']} "
              f"degraded={again['degraded']}")
        stats = _http_json(base + "/bank/stats")
        fallbacks = stats["counters"].get("serve.form_fallback", 0)
        check(fallbacks == 0,
              f"serve.form_fallback == {fallbacks}: a fused dispatch "
              "fell back to xla")
        check(stats["counters"].get("serve.degraded", 0) == 0,
              "serve.degraded != 0")
        out["bank"] = {"dispatches": stats["dispatches"],
                       "compiled_shapes": stats["compiled_shapes"],
                       "serve.form_fallback": fallbacks,
                       "cache": stats["cache"]}
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=30)
    check(not thread.is_alive(), "server thread did not stop")
    return out


def phase3_scale(out_dir: pathlib.Path, n_events: int = SCALE_EVENTS,
                 train_events: int = SCALE_TRAIN_EVENTS,
                 min_planted: int = SCALE_MIN_PLANTED,
                 require_tpu: bool = True) -> dict:
    """BASELINE config 4's code path: fit on the first window, stream
    the whole day through the fused on-device word+score+select."""
    import jax

    from onix.pipelines.scale import run_scale

    manifest = run_scale(n_events, train_events=train_events,
                         datatype="flow", n_sweeps=20,
                         out_path=out_dir / "scale_manifest.json")
    check(manifest["devices"] == [str(d) for d in jax.devices()],
          f"manifest devices {manifest['devices']} are not this "
          "process's devices")
    if require_tpu:
        check(all(d.platform == "tpu" for d in jax.devices()),
              f"not every device is a TPU: {manifest['devices']}")
    check(manifest["mesh"]["dp"] == len(jax.devices()),
          f"mesh {manifest['mesh']} on {len(jax.devices())} device(s)")
    check(manifest["words_mode"] == "device",
          f"words_mode {manifest['words_mode']!r}")
    # The form each sorted look-up took, as `build_flow_tables` said it
    # at the build (docs/OBSERVABILITY.md): none may be a search.
    from onix.utils import telemetry
    forms = [{k: s.attrs[k] for k in ("datatype", "word", "doc")}
             for s in telemetry.TRACER.spans() if s.name == "scan.tables"]
    check(bool(forms) and all(f[k] in ("compare", "join")
                              for f in forms for k in ("word", "doc")),
          f"look-up forms {forms}")
    rng = manifest["selected_score_range"]
    check(rng is not None and all(np.isfinite(rng)),
          f"selected_score_range {rng}")
    check(manifest["planted_in_bottom_k"] >= min_planted,
          f"planted_in_bottom_k {manifest['planted_in_bottom_k']} < "
          f"{min_planted}")
    keep = ("n_events", "train_events", "n_docs", "n_vocab",
            "n_train_tokens", "n_sweeps", "mesh", "devices", "words_mode",
            "planted_anomalies", "planted_in_bottom_k",
            "selected_score_range", "walls_seconds", "selection",
            "device_peak_bytes", "dp1_fast_path")
    return {**{k: manifest[k] for k in keep}, "lookup_forms": forms}


def run(out_dir: pathlib.Path, *, require_tpu: bool = True) -> dict:
    """All phases in order; returns the summary, which is also written
    to `out_dir` once phase 0 has found its device. A failed phase ends
    the run with `ok: false` and `failed_phase` named."""
    summary: dict = {"note": "smoke observation, not a benchmark: one "
                             "cold run, compile included",
                     "ok": False, "phases": {}}
    cache = CacheCounter()
    try:
        with tempfile.TemporaryDirectory(prefix="onix-chip-smoke-") as td:
            work = pathlib.Path(td)
            for name, phase in (
                    ("phase0_device", lambda: phase0_device(require_tpu)),
                    ("phase1_cli_day", lambda: phase1_cli_day(work)),
                    ("phase2_serve", lambda: phase2_serve(work)),
                    ("phase3_scale", lambda: phase3_scale(
                        out_dir, require_tpu=require_tpu))):
                summary["failed_phase"] = name
                before = cache.snapshot()
                t0 = time.monotonic()
                result = phase()
                wall = round(time.monotonic() - t0, 2)
                traffic = {k: v - before[k]
                           for k, v in cache.snapshot().items()}
                summary["phases"][name] = {"wall_seconds": wall,
                                           "compile_cache": traffic,
                                           "result": result}
                print(f"chip_smoke: {name} passed in {wall} s (smoke "
                      "observation, not a benchmark); compile cache "
                      f"{traffic}", flush=True)
        del summary["failed_phase"]
        summary["ok"] = True
    except Exception as e:                      # noqa: BLE001 — the
        # boundary: any phase's failure is reported and fails the run.
        traceback.print_exc()
        summary["error"] = f"{type(e).__name__}: {e}"[:2000]
        print(f"chip_smoke: FAILED in {summary['failed_phase']}: "
              f"{summary['error']}", file=sys.stderr)
    finally:
        if "phase0_device" in summary["phases"]:
            summary["compile_cache_total"] = cache.snapshot()
            out_dir.mkdir(parents=True, exist_ok=True)
            stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
            path = out_dir / f"chip_smoke-{stamp}.json"
            path.write_text(json.dumps(summary, indent=2) + "\n")
            print(f"chip_smoke: summary written to {path}", flush=True)
    return summary


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", type=pathlib.Path,
                    default=(pathlib.Path(__file__).resolve().parent
                             / "chiprun_out" / "chip_smoke"),
                    help="directory for the summary JSON and the scale "
                         "manifest")
    args = ap.parse_args(argv)
    summary = run(args.out)
    if not summary["ok"]:
        return 1        # the failure is on stderr; stdout gets no result
    device = summary["phases"]["phase0_device"]["result"]["device"]
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
