"""Plain reference for the stream's bounded document table: the LRU
eviction, in numpy. It imports nothing of the program.

When the table holds more than `max_docs` documents at a superstep's
end, the floor(0.75 max_docs) most recently seen survive: every
document seen after the batch of the last survivor's sighting, and of
those seen in that batch the lowest ids (the earliest first sightings),
as many as there is room for. Survivors keep their first-seen order
under new dense ids 0, 1, ...; their gamma rows and their stamps (the
batch that last held them) go with them, bit for bit; every row of the
store past the survivors holds the cold start, alpha + 1, which a
document met later (an evicted address that returns among them) starts
from.

`compare` is given the table as it stood when the eviction fired
(`keys`, `last_seen`, `gamma`, one entry a document; the stamps are the
caller's own, from the batches it handed over, and not the program's)
and what the program left for the next superstep (`keys`, and the
store's `gamma` and `last_seen` at its capacity), and counts the
documents where the two differ: `evict_mismatch`, exact.
"""

from __future__ import annotations

import numpy as np


def survivors(last_seen: np.ndarray, target: int) -> np.ndarray:
    """The ids that survive, ascending: the `target` seen most recently,
    ties to the lower id."""
    n = len(last_seen)
    if target >= n:
        return np.arange(n)
    cut = np.sort(last_seen)[n - target]        # the target-th latest stamp
    later = np.flatnonzero(last_seen > cut)
    tied = np.flatnonzero(last_seen == cut)[:target - len(later)]
    return np.union1d(later, tied)


def due(before: dict, max_docs: int) -> np.ndarray:
    return survivors(np.asarray(before["last_seen"]),
                     max(1, int(max_docs * 0.75)))


def compare(before: dict, after: dict, max_docs: int, alpha: float) -> dict:
    keep = due(before, max_docs)
    keys = np.asarray(before["keys"])[keep]
    rows = np.asarray(before["gamma"], np.float32)[keep]
    seen = np.asarray(before["last_seen"], np.int64)[keep]
    got_keys = np.asarray(after["keys"])
    got_rows = np.asarray(after["gamma"], np.float32)
    got_seen = np.asarray(after["last_seen"], np.int64)
    m = min(len(keys), len(got_keys), len(got_rows))
    bad = abs(len(keys) - len(got_keys))
    bad += int(((got_keys[:m] != keys[:m])
                | (got_rows[:m] != rows[:m]).any(axis=1)
                | (got_seen[:m] != seen[:m])).sum())
    bad += max(len(keys) - len(got_rows), 0)
    cold = got_rows[len(keys):]
    bad += int((cold != np.float32(alpha + 1.0)).any(axis=1).sum())
    return {"evict_mismatch": bad, "evict_before": len(before["keys"]),
            "evict_after": len(got_keys)}


def broken(before: dict, after: dict, max_docs: int, alpha: float,
           control: str) -> dict:
    """What a program broken as `control` says would have left."""
    n = len(before["keys"])
    keep = due(before, max_docs)
    seen = np.asarray(before["last_seen"])
    rows_from = keep
    if control == "evict_newest":
        # The least recent kept, the most recent quarter dropped.
        keep = np.sort(np.lexsort((np.arange(n), seen))[:len(keep)])
        rows_from = keep
    elif control == "rows_unmoved":
        rows_from = np.arange(len(keep))
    elif control == "stamps_unwritten":
        # Every stamp 0: the lowest ids survive, stamped 0.
        seen = np.zeros(n, np.int64)
        keep = rows_from = np.arange(len(keep))
    else:
        raise ValueError(f"unknown control {control!r}")
    out = {k: np.array(v) for k, v in after.items()}
    out["keys"] = np.asarray(before["keys"])[keep]
    out["gamma"][:len(keep)] = np.asarray(before["gamma"])[rows_from]
    out["last_seen"][:len(keep)] = seen[rows_from]
    return out
