"""Tier-1 smoke of the model-bank load harness (ISSUE 7 satellite;
the test_fit_gap_smoke discipline: the harness is the decision table
behind the bank's acceptance numbers and its TPU rows, so a tiny-shape
invocation runs in the fast suite and the harness cannot rot between
chip runs)."""

import json


def test_exp_model_bank_tiny_shape_runs_all_arms(tmp_path):
    from scripts.exp_model_bank import main

    out_path = tmp_path / "bank.json"
    rc = main(["--tenants", "4", "--requests", "12", "--events", "256",
               "--docs", "128", "--vocab", "96", "--capacity", "2",
               "--batch", "6", "--reps", "1", "--ladder", "4",
               "--out", str(out_path)])
    assert rc == 0
    doc = json.loads(out_path.read_text())
    # Every arm produced a rate, winners were bit-identical, and the
    # dispatch collapse is recorded (12 requests -> 2 banked batches).
    assert doc["parity_bit_identical"] is True
    for arm in ("sequential", "banked_vmap", "banked_gather"):
        assert doc["arms"][arm]["events_per_sec"] > 0, arm
    assert doc["arms"]["sequential"]["dispatches"] == 12
    assert doc["arms"]["banked_vmap"]["dispatches"] == 2
    assert doc["speedup_banked_vs_sequential"] > 0
    # The serving replay (bank of 4, capacity 2, windowed stream):
    # cache hits happened, churn happened, and the capped bank's
    # winners matched the uncapped run (the LRU proof).
    sr = doc["serving_replay"]
    assert sr["parity_bit_identical"] is True
    assert sr["capped_winners_identical_to_uncapped"] is True
    assert sr["banked"]["cache_hit_rate"] is not None
    assert sr["banked"]["cache_hit_rate"] > 0
    assert sr["banked"]["residency_churn"]["evicts"] > 0
    assert sr["banked"]["latency_p99_ms"] >= sr["banked"]["latency_p50_ms"]
    # The form-crossover ladder emitted both forms' rates.
    (row,) = doc["bank_size_ladder"]
    assert row["events_per_sec_vmap"] > 0
    assert row["events_per_sec_gather"] > 0
    # H2D staging is visible: one stacked transfer per table family
    # per admission boundary, tallied in the bank counters.
    assert doc["bank_counters"]["bank.h2d_transfers"] > 0
    assert doc["bank_counters"]["bank.h2d_bytes"] > 0
