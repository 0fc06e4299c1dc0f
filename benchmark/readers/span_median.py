"""Median length in seconds of the host spans named `spec["span"]`."""
import statistics


def read(run: dict, spec: dict):
    spans = run["spans"].spans.get(spec["span"], [])
    if not spans:
        return None
    return statistics.median(b - a for a, b in spans)
