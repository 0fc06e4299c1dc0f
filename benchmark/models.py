"""The yardstick: peaks of the chip and the work each kernel's algorithm
needs, computed from shapes alone.

Both byte models count what the algorithm has to move, whatever
implements it (scatter, one-hot matmul or a Pallas kernel for the sweep;
an f32 or a screened scan for the selection), so a later PR that swaps a
kernel does not make the count stale. They were copied from the program
(`onix.utils.obs.gibbs_sweep_bytes_per_token`, the 92 B/event scan model
of `bench.py`) so that no later PR can change them; the originals are
listed in PERF.md section 7 for deletion.
"""

from __future__ import annotations

import json
import pathlib

_HERE = pathlib.Path(__file__).resolve().parent


def load_peaks(device_kind: str, path: pathlib.Path | None = None) -> dict:
    """Peaks of the chip named `device_kind`; an unknown kind raises."""
    table = json.loads((path or _HERE / "peaks.json").read_text())
    for prefix, peaks in table.items():
        if not prefix.startswith("_") and device_kind.startswith(prefix):
            return dict(peaks, kind=prefix)
    raise LookupError(
        f"no peaks for device kind {device_kind!r}: add it to "
        "benchmark/peaks.json with its source")


def gibbs_sweep_bytes_per_token(n_topics: int) -> float:
    """One resampled token: read and write back its n_dk row and its n_wk
    row (4 rows of K int32) and stream its doc id, word id and z (12 B)."""
    return 4 * n_topics * 4 + 12


def gibbs_sweep_flops_per_token(n_topics: int) -> float:
    """Three logs, two adds and a compare per topic, as the collapsed
    conditional needs them: far under the byte bound (see binding_peak)."""
    return 8.0 * n_topics


def scan_bytes_per_event(staged_bytes: int, n_tokens: int) -> float:
    """One scored event: its staged columns read once, one 4-byte score
    table gather per token, and the 4-byte score handed to the
    selection. (A random 4-byte read costs the chip a whole burst and the
    id lookups probe their tables: the model counts what the algorithm
    needs, so the share reads low, never high.)"""
    return staged_bytes + n_tokens * 4 + 4


BYTES_MODELS = {
    "gibbs_sweep": lambda cfg: gibbs_sweep_bytes_per_token(cfg["n_topics"]),
    "stream_scan": lambda cfg: scan_bytes_per_event(
        cfg["staged_bytes_per_event"], cfg["tokens_per_event"]),
}
FLOPS_MODELS = {
    "gibbs_sweep": lambda cfg: gibbs_sweep_flops_per_token(cfg["n_topics"]),
    "stream_scan": lambda cfg: 2.0 * cfg["tokens_per_event"],
}


def least_seconds(model: str, cfg: dict, items: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for `items`: the larger of
    bytes over peak bytes/s and operations over peak FLOP/s, and which."""
    by_bytes = BYTES_MODELS[model](cfg) * items / peaks["hbm_bytes_per_s"]
    by_flops = FLOPS_MODELS[model](cfg) * items / peaks["bf16_flops_per_s"]
    return (by_bytes, "hbm") if by_bytes >= by_flops else (by_flops, "flops")
