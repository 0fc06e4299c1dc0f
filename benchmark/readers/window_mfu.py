"""The whole step's share of the chip's binding peak, in percent: the
least time the chip could take for every item the traced window
finished, over the traced window's wall time (idle time and every other
program included)."""
from benchmark import models


def read(run: dict, spec: dict):
    red, peaks = run.get("trace_summary"), run.get("peaks")
    if not red or not peaks or red["window_s"] <= 0:
        return None
    calls = sum(m["whole"] for name, m in red["modules"].items()
                if spec["module_match"] in name)
    if not calls:
        return None
    items = calls * run["window"]["items_per_call"]
    least, _ = models.least_seconds(spec["model"], run["config"], items, peaks)
    return 100.0 * least / red["window_s"]
