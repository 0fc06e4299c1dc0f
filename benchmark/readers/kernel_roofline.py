"""A program's share of its roofline, in percent: the least time the
chip could take for the items its traced executions handled (the byte
and operation models of `benchmark/models.py`, the peaks of
`benchmark/peaks.json`) over the device time of the programs whose name
holds `spec["module_match"]`. Only executions that lie whole inside the
traced window count. Nothing to read gives nothing, never 0."""
from benchmark import models


def read(run: dict, spec: dict):
    red, peaks = run.get("trace_summary"), run.get("peaks")
    if not red or not peaks:
        return None
    hit = [m for name, m in red["modules"].items()
           if spec["module_match"] in name and m["whole"] > 0]
    seconds = sum(m["whole_seconds"] for m in hit)
    calls = sum(m["whole"] for m in hit)
    if not calls or seconds <= 0:
        return None
    items = calls * run["window"]["items_per_call"]
    least, _ = models.least_seconds(spec["model"], run["config"], items, peaks)
    return 100.0 * least / seconds
