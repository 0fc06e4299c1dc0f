"""A counter of the program over the timed window, as the driver read
it at the window's two ends (`run["window"]["counters"]`: the growth of
`onix.utils.obs.counters` under `spec["counter"]`), over the window's
count of `spec["per"]` (a key of `run["window"]`). A driver that read no
such counter gives nothing."""


def read(run: dict, spec: dict):
    window = run.get("window", {})
    value = window.get("counters", {}).get(spec["counter"])
    per = window.get(spec["per"])
    if value is None or not per:
        return None
    return value / per
