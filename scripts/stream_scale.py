"""STREAM evidence artifact for BASELINE configs[4] — streaming
online-VB LDA over ingest minibatches (incremental scoring).

The capability claim this measures (onix/pipelines/streaming.py
docstring; the reference re-fits once per day, so a beacon starting at
09:00 is invisible until tomorrow's batch): a campaign that APPEARS
MID-STREAM is alerted within the very batches it occurs in, while the
stream sustains ingest-rate throughput with bounded state.

Per-cell measurements:
  * events/s through word-create + SVI update + incremental scoring
    (model-pipeline only; synthesis timed separately in serial feed
    mode, riding the prefetch worker arm in overlap mode — the role
    file decode plays in production),
  * detection: fraction of planted campaign events alerted in their
    OWN batch (zero-lag), split by stream phase,
  * false-alert rate on clean warmup batches after burn-in,
  * state bounds: compiled-shape count, checkpoint bytes, doc count
    under pipeline.stream_max_docs,
  * r10 pipeline shape: dispatch counts, stage walls incl. prefetch
    overlap/wait, shape-lattice stats, prefetch mode/occupancy.

r10 arms (ISSUE 5; r06 artifacts used the default serial per-batch
protocol):

    # r06-comparable baseline protocol (per-batch, serial feed)
    python scripts/stream_scale.py --out docs/STREAM_r10_perbatch.json
    # fused supersteps, serial feed (dispatch-collapse arm)
    python scripts/stream_scale.py --superstep 8 \
        --out docs/STREAM_r10_superstep.json
    # production protocol: pre-landed files, supersteps + depth-k
    # read+convert pipeline (the run_stream shape)
    python scripts/stream_scale.py --superstep 8 --feed files \
        --out docs/STREAM_r10_files.json
"""
import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


class _FileItem:
    """Picklable read work unit for the files feed: the production
    protocol — the feed is pre-landed on disk and the prefetch worker
    pays read+convert, exactly what run_stream's DecodeItem pays."""

    def __init__(self, path):
        self.path = str(path)

    def __call__(self):
        import pandas as pd
        return pd.read_parquet(self.path)


class _SynthItem:
    """Picklable synth work unit for the overlap feed: producing the
    batch ON the prefetch worker plays the role file decode plays in
    run_stream. The planted-anomaly indices ride the frame's attrs
    (they survive pickling) so detection bookkeeping stays exact."""

    def __init__(self, datatype, n_events, n_hosts, n_anomalies, seed):
        self.datatype = datatype
        self.n_events = n_events
        self.n_hosts = n_hosts
        self.n_anomalies = n_anomalies
        self.seed = seed

    def __call__(self):
        from onix.pipelines.synth import SYNTH
        day, planted = SYNTH[self.datatype](
            n_events=self.n_events, n_hosts=self.n_hosts,
            n_anomalies=self.n_anomalies, seed=self.seed)
        day.attrs["planted"] = np.asarray(planted)
        return day


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=60)
    ap.add_argument("--batch-events", type=int, default=250_000)
    ap.add_argument("--attack-from", type=int, default=30,
                    help="first batch index carrying the campaign")
    ap.add_argument("--attack-events", type=int, default=60)
    ap.add_argument("--max-docs", type=int, default=4096)
    ap.add_argument("--datatype", default="flow")
    ap.add_argument("--superstep", type=int, default=0,
                    help="chain S minibatch updates per fused dispatch "
                         "(0/1 = the per-batch r06 path)")
    ap.add_argument("--feed", choices=("serial", "overlap", "files"),
                    default="serial",
                    help="serial: synth on the consumer, timed apart "
                         "(the r03/r06 protocol); overlap: synth+convert "
                         "ride the depth-k prefetch pipeline; files: the "
                         "PRODUCTION protocol — the feed is pre-landed "
                         "on disk (synth timed apart, like serial) and "
                         "prefetch workers pay read+convert, exactly "
                         "what run_stream's DecodeItem pays")
    ap.add_argument("--prefetch-depth", type=int, default=None)
    ap.add_argument("--prefetch-mode", default=None,
                    choices=("auto", "thread", "process"))
    ap.add_argument("--warm-iters", type=int, default=None,
                    help="lda.svi_warm_iters override (the warm/cold "
                         "E-step split; -1 auto = 4 for streaming)")
    ap.add_argument("--set", action="append", default=[],
                    dest="overrides", metavar="KEY=VALUE",
                    help="extra dotted-path config overrides, e.g. "
                         "--set lda.stream_estep=scvb0 (the r11 SCVB0 "
                         "arm; repeatable)")
    ap.add_argument("--out", default="docs/STREAM_r10.json")
    args = ap.parse_args()

    import tempfile

    import jax

    from onix.config import load_config
    from onix.pipelines.streaming import ColumnPrefetcher, StreamingScorer
    from onix.utils.obs import enable_compile_cache, print_device

    enable_compile_cache()
    print_device()
    ck_root = pathlib.Path(tempfile.mkdtemp(prefix="onix-stream-"))
    overrides = [
        f"pipeline.stream_max_docs={args.max_docs}",
        f"pipeline.stream_superstep={args.superstep}",
        "lda.checkpoint_every=10",
    ]
    if args.prefetch_depth is not None:
        overrides.append(
            f"pipeline.stream_prefetch_depth={args.prefetch_depth}")
    if args.prefetch_mode is not None:
        overrides.append(
            f"pipeline.stream_prefetch_mode={args.prefetch_mode}")
    if args.warm_iters is not None:
        overrides.append(f"lda.svi_warm_iters={args.warm_iters}")
    overrides.extend(args.overrides)
    cfg = load_config(None, overrides)
    scorer = StreamingScorer(cfg, args.datatype, checkpoint_dir=ck_root,
                             max_docs=args.max_docs)

    def item_for(b):
        attack = b >= args.attack_from
        return _SynthItem(args.datatype, args.batch_events,
                          max(120, args.batch_events // 250),
                          args.attack_events if attack else 1, 1000 + b)

    synth_wall = 0.0
    pipe_wall = 0.0
    n_total = 0
    det_rows = []          # per attack batch: planted, caught-in-batch
    clean_alert_rates = []
    ck_bytes = []
    group = max(1, scorer.superstep)

    def account(b, res, planted):
        nonlocal n_total
        n_total += res.n_events
        alerted = set(res.alerts["event_idx"].tolist())
        plant_set = set(np.asarray(planted).tolist())
        hit = len(alerted & plant_set)
        if b >= args.attack_from:
            det_rows.append({"batch": b, "planted": len(plant_set),
                             "caught_in_batch": hit})
        elif b >= 10:
            # Post-burn-in clean phase. The generator still plants one
            # anomaly (its heterogeneity floor) — alerting IT is a
            # correct detection, so the false-alert rate counts only
            # non-planted alerts.
            clean_alert_rates.append(
                len(alerted - plant_set) / res.n_events)
        if (b + 1) % 10 == 0:
            size = sum(f.stat().st_size for f in ck_root.rglob("*")
                       if f.is_file())
            ck_bytes.append(size)
            print(f"[batch {b}] docs={scorer.docs.n_docs} "
                  f"shapes={len(scorer.pad_shapes)} ckpt={size}B "
                  f"events/s={n_total / max(pipe_wall, 1e-9):,.0f}",
                  flush=True)

    if args.feed == "serial":
        buf, buf_planted, b_done = [], [], 0
        for b in range(args.batches):
            t0 = time.monotonic()
            day = item_for(b)()
            synth_wall += time.monotonic() - t0
            buf.append((day, None))
            buf_planted.append(day.attrs["planted"])
            if len(buf) >= group or b == args.batches - 1:
                t0 = time.monotonic()
                results = scorer.process_many(buf)
                np.asarray(results[-1].scores)      # settle device work
                pipe_wall += time.monotonic() - t0
                for res, planted in zip(results, buf_planted):
                    account(b_done, res, planted)
                    b_done += 1
                buf, buf_planted = [], []
    else:
        if args.feed == "files":
            # Pre-land the feed (synth timed apart, as in serial); the
            # timed loop then pays read+convert on the worker arm —
            # run_stream's production shape.
            feed_dir = pathlib.Path(tempfile.mkdtemp(prefix="onix-feed-"))
            items = []
            planted_by_batch = []
            for b in range(args.batches):
                t0 = time.monotonic()
                day = item_for(b)()
                # attrs don't survive parquet (and pyarrow chokes on
                # the ndarray) — planted stays host-side, order-keyed.
                planted = day.attrs.pop("planted")
                p = feed_dir / f"batch{b:04d}.parquet"
                day.to_parquet(p)
                synth_wall += time.monotonic() - t0
                planted_by_batch.append(planted)
                items.append(_FileItem(p))
        else:
            items = [item_for(b) for b in range(args.batches)]
            planted_by_batch = None
        pre = ColumnPrefetcher(scorer, items)
        buf, buf_planted, b_done = [], [], 0
        b_in = 0
        t_loop = time.monotonic()
        for table, cols in pre:
            buf.append((table, cols))
            buf_planted.append(planted_by_batch[b_in]
                               if planted_by_batch is not None
                               else table.attrs["planted"])
            b_in += 1
            if len(buf) >= group:
                results = scorer.process_many(buf)
                np.asarray(results[-1].scores)
                pipe_wall = time.monotonic() - t_loop
                for res, planted in zip(results, buf_planted):
                    account(b_done, res, planted)
                    b_done += 1
                buf, buf_planted = [], []
        if buf:
            results = scorer.process_many(buf)
            np.asarray(results[-1].scores)
            for res, planted in zip(results, buf_planted):
                account(b_done, res, planted)
                b_done += 1
        pipe_wall = time.monotonic() - t_loop
        if args.feed == "overlap":
            synth_wall = None   # rides the prefetch worker arm

    caught = sum(r["caught_in_batch"] for r in det_rows)
    plant = sum(r["planted"] for r in det_rows)
    ps = dict(scorer.prefetch_stats)
    if ps.get("resolves"):
        ps["occupancy_mean"] = round(
            ps["occupancy_sum"] / max(ps["resolves"], 1), 2)
    doc = {
        "config": "BASELINE configs[4] (streaming online-VB over minibatches)",
        "datatype": args.datatype,
        "n_batches": args.batches,
        "events_per_batch": args.batch_events,
        "n_events_total": n_total,
        "device": str(jax.devices()[0]),
        "events_per_second_pipeline_only": round(
            n_total / max(pipe_wall, 1e-9), 1),
        # r10 pipeline shape under measurement.
        "arm": {"feed": args.feed,
                "superstep": group,
                "svi_warm_iters_effective":
                    scorer._lda_eff.svi_warm_iters,
                "prefetch": ps or None},
        "dispatches": dict(scorer.dispatches),
        "shape_stats": dict(scorer.shape_stats),
        # Which word path each batch rode: "device" = fused on-device
        # binning+packing+bucketing with the deduped weighted SVI path
        # (the default), "host" = the reference builders
        # (ONIX_HOST_WORDS=1 forces it — the cross-check arm).
        "words_mode_batches": dict(scorer.words_mode_batches),
        "pipeline_stage_walls_seconds": {
            k: round(v, 2) for k, v in scorer.stage_walls.items()},
        "walls_seconds": {"synthesize": (round(synth_wall, 2)
                                         if synth_wall is not None
                                         else "overlapped (worker arm)"),
                          "pipeline": round(pipe_wall, 2)},
        "zero_lag_detection": {
            "campaign_from_batch": args.attack_from,
            "planted_total": plant,
            "caught_in_own_batch": caught,
            "rate": round(caught / max(plant, 1), 4),
            "per_batch": (det_rows if len(det_rows) <= 7
                          else det_rows[:5] + det_rows[-2:]),
        },
        "clean_batch_alert_rate_mean": (
            round(float(np.mean(clean_alert_rates)), 6)
            if clean_alert_rates else None),
        "bounded_state": {
            "stream_max_docs": args.max_docs,
            "docs_after": int(scorer.docs.n_docs),
            "compiled_shape_pairs": len(scorer.pad_shapes),
            "checkpoint_bytes_over_time": ck_bytes,
        },
    }
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=2) + "\n")
    print(json.dumps({k: doc[k] for k in (
        "events_per_second_pipeline_only", "zero_lag_detection",
        "clean_batch_alert_rate_mean")}, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
