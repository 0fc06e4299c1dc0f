"""Another reader's number over the batches one call of the program
runs (`run["window"]["batches_per_call"]`): `spec["of"]` is that
reader's own spec. A superstep of the stream runs several minibatches
in one execution and stages them in one span; the per-batch metrics
read a whole execution, or a whole span, and divide. Nothing to read,
or a driver that states no such count, gives nothing."""


def read(run: dict, spec: dict):
    inner = spec["of"]
    value = run["manifest"].load("readers", inner["reader"]).read(run, inner)
    per = run.get("window", {}).get("batches_per_call")
    if value is None or not per:
        return None
    return value / per
