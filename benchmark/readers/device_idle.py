"""Share of the traced window in which no operation ran on the device."""


def read(run: dict, spec: dict):
    red = run.get("trace_summary")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
