"""The yardstick: byte models against hand counts, the table of peaks,
and the trace reduction on a small recorded trace."""

import json
import pathlib

import pytest

from benchmark import models, tracered

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


def test_byte_models_match_hand_counts():
    # K=20: two count rows read and written back (4 x 20 x 4 B) + d, w, z.
    assert models.gibbs_sweep_bytes_per_token(20) == 332
    # flow: 8 staged 4-byte columns + 2 table gathers + the score.
    assert models.scan_bytes_per_event(32, 2) == 44
    # dns: 6 staged 4-byte columns + 1 table gather + the score.
    assert models.scan_bytes_per_event(24, 1) == 32
    peaks = models.load_peaks("TPU v5 lite")
    least, bound = models.least_seconds(
        "gibbs_sweep", {"n_topics": 20}, 819e9 / 332, peaks)
    assert bound == "hbm" and least == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    assert models.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(LookupError):
        models.load_peaks("TPU v9 imaginary")
    with pytest.raises(LookupError):
        models.load_peaks("cpu")


def test_trace_reduction_names_gaps_on_a_handmade_trace():
    planes = json.loads((FIXTURES / "trace_small.json").read_text())
    red = tracered.reduce_planes(planes["planes"])
    want = planes["expect"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    for name, seconds in want["module_seconds"].items():
        assert red["modules"][name]["seconds"] == pytest.approx(seconds)
        assert red["modules"][name]["whole"] == want["module_whole"][name]
        # Executions cut by the window's ends are left out of the roofline.
        assert red["modules"][name]["whole_seconds"] == pytest.approx(
            want["module_whole_seconds"][name])
    assert red["idle_gaps"][0][0] == want["longest_gap_phase"]
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        want["window_s"] - want["busy_s"])


def test_a_trace_without_the_window_marks_is_refused():
    planes = json.loads((FIXTURES / "trace_small.json").read_text())["planes"]
    for p in planes:
        for ln in p["lines"]:
            ln["events"] = [e for e in ln["events"]
                            if e[0] != "bench.trace_close"]
    with pytest.raises(ValueError):
        tracered.reduce_planes(planes)


def test_trace_reduction_on_a_trace_recorded_on_the_chip():
    rec = json.loads((FIXTURES / "trace_v5e_clip.json").read_text())
    red = tracered.reduce_planes(rec["planes"])
    want = rec["expect"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    for name, seconds in want["module_seconds"].items():
        assert red["modules"][name]["seconds"] == pytest.approx(seconds)
    sweep = [m for n, m in red["modules"].items() if "superstep" in n]
    assert len(sweep) == 1 and sweep[0]["whole"] == 0   # cut by the clip
    assert sum(s for _, s in red["device_ops"]) == pytest.approx(
        red["busy_s"], rel=1e-6)                        # self times tile it


def test_union_and_gaps():
    assert tracered.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert tracered.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
