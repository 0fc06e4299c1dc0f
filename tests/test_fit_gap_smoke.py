"""Tier-1 smoke of the fit-gap isolation harness (scripts/exp_fit_gap.py).

The harness is the decision table behind the n_wk matmul gate and the
superstep adoption (docs/PERF.md "the gibbs_fit vs sweep-microbench
gap"), but its full shapes only run on the chip — runs that
can be weeks apart. This tiny-shape invocation (n_docs≈200, V≈64-scale)
runs in the fast suite so the harness cannot rot in between: every arm
must execute, emit its rate, and the superstep arm must stay
bit-identical to the per-sweep loop (asserted inside the script).
"""

import json


def test_exp_fit_gap_tiny_shape_runs_all_arms(tmp_path):
    from scripts.exp_fit_gap import main

    out_path = tmp_path / "fitgap.json"
    rc = main(["4000", "--hosts", "200", "--sweeps", "2",
               "--block", "512", "--k-sweep", "4,8",
               "--out", str(out_path)])
    assert rc == 0
    doc = json.loads(out_path.read_text())
    # Tiny shape, as specified: ~200 docs, small product vocabulary.
    assert doc["n_docs"] == 200
    assert doc["n_vocab"] < 1024
    # Every isolation arm produced a number (the rot this smoke
    # prevents is an arm silently breaking between TPU windows).
    for arm in ("sharded_dp1_fast", "sharded_dp1_shardmap",
                "plain_single", "all_accumulate", "no_accumulate",
                "per_sweep_loop", "superstep_loop", "raw_sweeps_no_fit",
                "raw_nwk_scatter", "raw_nwk_matmul", "raw_nwk_pallas"):
        assert doc[arm]["wall_s"] >= 0.0, arm
    assert doc["nwk_collision_density"] > 0
    # The three count-update forms were asserted bit-identical at this
    # run's shape inside the script.
    assert doc["nwk_forms_bit_identical"] is True
    # The r11 sampler-form arms ran at every requested K, emitted both
    # rates, and held the perplexity-band parity (asserted in-script).
    assert set(doc["sampler_k_sweep"]) == {"4", "8"}
    for row in doc["sampler_k_sweep"].values():
        assert row["dense_mtok_per_s"] > 0
        assert row["sparse_mtok_per_s"] > 0
        assert row["n_active"] >= 1
    assert doc["sampler_parity_ll_band"] is True
