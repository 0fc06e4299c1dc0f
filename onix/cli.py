"""onix command-line interface.

Mirrors the reference's operator surface (SURVEY.md §3.1, §7.1.8):
`ml_ops.sh <YYYYMMDD> <flow|dns|proxy> [TOL] [MAXRESULTS]` becomes
`onix score <date> <type> [--tol] [--max-results]`, plus `ingest` and
`oa` subcommands for the other two pillars (reference README.md:35-48).

Subcommands are registered lazily so `onix config` works before the
heavier pipeline modules import JAX.
"""

from __future__ import annotations

import argparse
import sys

from onix.config import load_config


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--config", "-c", default=None,
                   help="YAML/JSON config file")
    p.add_argument("--set", "-s", action="append", default=[],
                   metavar="KEY.PATH=VALUE", dest="overrides",
                   help="config override (repeatable)")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="onix",
        description="TPU-native network-security analytics (ONI on XLA)")
    sub = parser.add_subparsers(dest="command", required=True)

    p_cfg = sub.add_parser("config", help="print the resolved configuration")
    _add_common(p_cfg)

    p_score = sub.add_parser(
        "score", help="run the suspicious-connects scoring pipeline for one "
                      "day of one datatype (the ml_ops.sh equivalent)")
    _add_common(p_score)
    p_score.add_argument("date", help="day to score, YYYY-MM-DD")
    p_score.add_argument("datatype", choices=("flow", "dns", "proxy"))
    p_score.add_argument("--tol", type=float, default=None)
    p_score.add_argument("--max-results", type=int, default=None)
    p_score.add_argument("--engine", choices=("gibbs", "svi", "sharded"),
                         default="gibbs",
                         help="gibbs: single-device batched collapsed "
                              "Gibbs; svi: online VB; sharded: multi-"
                              "chip doc/vocab-sharded Gibbs over the "
                              "mesh.dp x mesh.mp mesh")
    p_score.add_argument("--fault-inject", type=int, default=None,
                         metavar="SWEEP",
                         help="testing hook: simulate a preemption after "
                              "this sweep (re-run resumes from checkpoint)")
    p_score.add_argument("--fault-plan", default=None, metavar="PLAN",
                         help="chaos drill: declarative fault plan, e.g. "
                              "'fit:sweep@8=preempt,ckpt:save@1=torn' "
                              "(docs/ROBUSTNESS.md; also env "
                              "ONIX_FAULT_PLAN)")

    p_ingest = sub.add_parser(
        "ingest", help="decode and load raw telemetry into the store")
    _add_common(p_ingest)
    p_ingest.add_argument("datatype", choices=("flow", "dns", "proxy"))
    p_ingest.add_argument("paths", nargs="+", help="raw capture/log files")

    p_watch = sub.add_parser(
        "watch", help="watch a landing directory and ingest new files; "
                      "--procs fans out over worker processes (run the "
                      "same command on N hosts sharing the directory to "
                      "scale out)")
    _add_common(p_watch)
    p_watch.add_argument("datatype", choices=("flow", "dns", "proxy"))
    p_watch.add_argument("landing_dir")
    p_watch.add_argument("--procs", type=int, default=1,
                         help="worker processes (1 = in-process watcher)")
    p_watch.add_argument("--max-seconds", type=float, default=None,
                         help="stop after this long (default: forever)")
    p_watch.add_argument("--drain", action="store_true",
                         help="exit once a poll finds nothing to claim")

    p_stream = sub.add_parser(
        "stream", help="streaming scoring: online-VB model updated and "
                       "scored per ingest minibatch (one file = one batch)")
    _add_common(p_stream)
    p_stream.add_argument("datatype", choices=("flow", "dns", "proxy"))
    p_stream.add_argument("paths", nargs="+", help="raw telemetry files, "
                          "consumed in order as minibatches")
    p_stream.add_argument("--buckets", type=int, default=1 << 15,
                          help="hashed vocabulary size (static V)")
    p_stream.add_argument("--epochs", type=int, default=1,
                          help="replay the file list N times (burn-in)")
    p_stream.add_argument("--superstep", type=int, default=None,
                          metavar="S",
                          help="chain S minibatch updates (E-step + "
                               "lambda step + scoring) in ONE jitted "
                               "dispatch, winners fetched once per "
                               "superstep (pipeline.stream_superstep; "
                               "0/1 = per-batch)")
    p_stream.add_argument("--prefetch-depth", type=int, default=None,
                          metavar="K",
                          help="host pipeline depth: decode+convert up "
                               "to K batches ahead of the device step "
                               "(pipeline.stream_prefetch_depth)")
    p_stream.add_argument("--prefetch-mode", default=None,
                          choices=("auto", "thread", "process"),
                          help="where the host stage runs; auto "
                               "measures conversion wall vs pickle "
                               "round-trip on the first batch "
                               "(pipeline.stream_prefetch_mode)")
    p_stream.add_argument("--fault-plan", default=None, metavar="PLAN",
                          help="chaos drill: declarative fault plan, e.g. "
                               "'stream:batch@3=raise' (docs/ROBUSTNESS.md)")

    p_oa = sub.add_parser(
        "oa", help="operational analytics: enrich scored results for the UI")
    _add_common(p_oa)
    p_oa.add_argument("date", help="day to process, YYYY-MM-DD")
    p_oa.add_argument("datatype", choices=("flow", "dns", "proxy"))

    p_serve = sub.add_parser(
        "serve", help="serve the analyst dashboards + feedback endpoint "
                      "(the reference's notebook file server on :8889)")
    _add_common(p_serve)
    p_serve.add_argument("--port", type=int, default=8889)
    p_serve.add_argument("--host", default="127.0.0.1")
    p_serve.add_argument("--models-dir", default=None,
                         help="fitted-model bank root for the /score "
                              "endpoint (serving.models_dir; default "
                              "<store.root>/models — populate with "
                              "`onix score ... -s serving.save_fitted"
                              "=true`)")
    p_serve.add_argument("--bank-capacity", type=int, default=None,
                         help="resident tenants per bank shape class; "
                              "larger banks LRU-evict at request "
                              "boundaries (serving.bank_capacity)")

    p_label = sub.add_parser(
        "label", help="label OA results by rank (headless analyst feedback; "
                      "the dashboard Save button does the same via POST)")
    _add_common(p_label)
    p_label.add_argument("date", help="day, YYYY-MM-DD")
    p_label.add_argument("datatype", choices=("flow", "dns", "proxy"))
    p_label.add_argument("ranks", type=int, nargs="+",
                         help="dashboard rank numbers to label")
    p_label.add_argument("--label", type=int, required=True,
                         choices=(1, 2, 3),
                         help="1 high threat, 2 medium, 3 benign (only "
                              "benign rows bias the next model run)")

    p_setup = sub.add_parser(
        "setup", help="create the store layout and archive the config "
                      "(the oni-setup equivalent; idempotent)")
    _add_common(p_setup)

    p_demo = sub.add_parser(
        "demo", help="one-command end-to-end demo: synthesize the "
                     "2016-07-08 day, ingest, score, enrich, serve")
    _add_common(p_demo)
    p_demo.add_argument("--events", type=int, default=20000,
                        help="synthetic events per datatype")
    p_demo.add_argument("--generator", choices=("mixture", "sessions"),
                        default="mixture",
                        help="telemetry source: role-mixture synth or "
                             "the independent session/state-machine "
                             "generator")
    p_demo.add_argument("--serve", action="store_true",
                        help="serve the dashboards when done")
    p_demo.add_argument("--port", type=int, default=8889)

    return parser


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    cfg = load_config(args.config, args.overrides)

    # r18: route the telemetry layer (enablement, sampling, the
    # flight-recorder dump dir) from the resolved config for EVERY
    # command — a chaos drill on `onix score --fault-plan ...` must
    # land its postmortem under <store.root>/telemetry, not count an
    # unrouted dump.
    from onix.utils import telemetry
    telemetry.apply_config(cfg.telemetry)

    if args.command in ("score", "stream", "demo", "serve"):
        # Device-touching commands: persist compiled programs so daily
        # runs and server restarts never re-pay cold-compile
        # (obs.enable_compile_cache).
        from onix.utils.obs import enable_compile_cache
        enable_compile_cache()

    if args.command == "config":
        print(cfg.to_json())
        return 0

    if args.command == "score":
        cfg.pipeline.date = args.date
        cfg.pipeline.datatype = args.datatype
        if args.tol is not None:
            cfg.pipeline.tol = args.tol
        if args.max_results is not None:
            cfg.pipeline.max_results = args.max_results
        cfg.validate()          # re-check: flags bypass load_config's pass
        if args.fault_inject is not None:
            if args.engine != "gibbs":
                raise SystemExit(
                    "--fault-inject is only wired to the gibbs engine; "
                    f"a {args.engine} drill would silently do nothing")
            import os
            os.environ["ONIX_FAULT_SWEEP"] = str(args.fault_inject)
        if args.fault_plan is not None:
            from onix.utils import faults
            faults.install_plan(args.fault_plan)    # parse errors exit now
        from onix.pipelines.run import run_scoring
        return run_scoring(cfg, engine=args.engine)

    if args.command == "ingest":
        from onix.ingest.run import run_ingest
        return run_ingest(cfg, args.datatype, args.paths)

    if args.command == "watch":
        if args.procs > 1:
            from onix.ingest.mpingest import run_workers
            stats = run_workers(cfg, args.datatype, args.landing_dir,
                                n_procs=args.procs,
                                max_seconds=args.max_seconds,
                                idle_exit=args.drain)
            print(f"onix watch: {stats['files']} files, {stats['rows']} "
                  f"rows, {stats['errors']} errors, "
                  f"{stats.get('retries', 0)} retries, "
                  f"{stats.get('quarantined', 0)} quarantined, "
                  f"{stats.get('salvaged', 0)} salvaged "
                  f"({stats['workers']} workers)")
            return 1 if stats["errors"] else 0
        import time as time_mod
        from onix.ingest.watcher import IngestWatcher
        w = IngestWatcher(cfg, args.datatype, args.landing_dir,
                          require_stable=not args.drain)
        if args.drain:
            # Drain until nothing dispatches AND no failed file is
            # still inside its retry budget — a drain must carry every
            # failure to its salvage-or-quarantine verdict, not abandon
            # it mid-backoff for the next invocation.
            t0 = time_mod.monotonic()
            while True:
                dispatched = w.poll_once()
                if not dispatched and not w.pending_retries():
                    break
                if (args.max_seconds is not None
                        and time_mod.monotonic() - t0 > args.max_seconds):
                    break
                if not dispatched:
                    time_mod.sleep(min(w.poll_interval, 0.2))
        else:
            w.run(max_seconds=args.max_seconds)
        print(f"onix watch: {w.stats['files']} files, {w.stats['rows']} "
              f"rows, {w.stats['errors']} errors, "
              f"{w.stats['retries']} retries, "
              f"{w.stats['quarantined']} quarantined, "
              f"{w.stats['salvaged']} salvaged")
        return 1 if w.stats["errors"] else 0

    if args.command == "stream":
        if args.fault_plan is not None:
            from onix.utils import faults
            faults.install_plan(args.fault_plan)
        if args.superstep is not None:
            cfg.pipeline.stream_superstep = args.superstep
        if args.prefetch_depth is not None:
            cfg.pipeline.stream_prefetch_depth = args.prefetch_depth
        if args.prefetch_mode is not None:
            cfg.pipeline.stream_prefetch_mode = args.prefetch_mode
        cfg.validate()          # re-check: flags bypass load_config's pass
        from onix.pipelines.streaming import run_stream
        return run_stream(cfg, args.datatype, args.paths,
                          n_buckets=args.buckets, epochs=args.epochs)

    if args.command == "oa":
        from onix.oa.engine import run_oa
        return run_oa(cfg, args.date, args.datatype)

    if args.command == "serve":
        if args.models_dir is not None:
            cfg.serving.models_dir = args.models_dir
        if args.bank_capacity is not None:
            cfg.serving.bank_capacity = args.bank_capacity
        cfg.validate()          # re-check: flags bypass load_config's pass
        from onix.oa.serve import run_serve
        return run_serve(cfg, port=args.port, host=args.host)

    if args.command == "setup":
        from onix.setup_cmd import run_setup
        return run_setup(cfg)

    if args.command == "demo":
        from onix.setup_cmd import run_demo
        return run_demo(cfg, n_events=args.events, serve=args.serve,
                        port=args.port, generator=args.generator)

    if args.command == "label":
        from onix.oa.feedback import label_by_rank
        path = label_by_rank(cfg, args.datatype, args.date, args.ranks,
                             args.label)
        print(f"onix label: {len(args.ranks)} rows -> {path}")
        return 0

    return 2


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:     # e.g. `onix config | head`
        sys.exit(0)
