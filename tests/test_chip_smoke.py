"""chip_smoke.py's contract, as far as a CPU can check it (ISSUE 21):
the phase functions hold at toy event counts, the script refuses to run
without a chip, the compile cache is placed from outside, and a fit
fabric coordinated from an accelerator-holding process raises."""

import os
import pathlib
import subprocess
import sys
from unittest import mock

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def _restore_process_globals():
    """`onix.cli.main` and `make_server` route the process-global
    telemetry singletons at the run's (temporary) store; later test
    modules must not inherit that. And the phases read the
    process-global counters (`bank.cache_hit` is phase 2's
    `cache.hits`), so they start from zero whatever test files the
    worker has run before this one."""
    from onix.utils import telemetry
    from onix.utils.obs import counters
    counters.reset()
    yield
    counters.reset()
    telemetry.reset_for_tests()


def test_phases_hold_at_toy_event_counts(tmp_path):
    """Phases 1-3 through the same functions the chip run calls; only
    the event counts (and the recall bar that scales with them) are
    cut. K, vocabularies, sweeps and block sizes are the product's.
    (Phase 0 is the refusal test below.)"""
    day = chip_smoke.phase1_cli_day(tmp_path, n_events=1500)
    assert set(day) == {"flow", "dns", "proxy"}
    assert all(d["n_results"] > 0 for d in day.values())

    served = chip_smoke.phase2_serve(tmp_path, n_events=256,
                                     max_results=50)
    assert served["bank"]["serve.form_fallback"] == 0
    assert served["bank"]["cache"]["hits"] == 1

    scale = chip_smoke.phase3_scale(tmp_path, n_events=12_000,
                                    train_events=6_000, min_planted=1,
                                    require_tpu=False)
    assert scale["words_mode"] == "device"
    assert scale["selection"] == {"screened_scans": 0,
                                  "screened_uncertified": 0}


def test_script_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout     # no result line


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    import jax

    from onix.utils.obs import enable_compile_cache

    def dirs_set(update):
        return [c.args[1] for c in update.call_args_list
                if c.args[0] == "jax_compilation_cache_dir"]

    with mock.patch.object(jax.config, "update") as update:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        enable_compile_cache()
        assert dirs_set(update) == []       # JAX reads the variable itself
    with mock.patch.object(jax.config, "update") as update:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        enable_compile_cache()
        assert dirs_set(update) == [str(REPO / ".jax_cache")]


def test_fabric_refuses_an_accelerator_holding_coordinator(tmp_path):
    """A TPU parent with (default) CPU fit workers raises before
    anything is spawned — the fit would otherwise run off-device while
    the manifest lists TPU devices."""
    import jax

    from onix.config import LDAConfig
    from onix.corpus import Corpus
    from onix.parallel import hostfabric

    corpus = Corpus(doc_ids=[0, 1], word_ids=[0, 1], n_docs=2, n_vocab=2)
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        with pytest.raises(hostfabric.FabricError, match="JAX_PLATFORMS=cpu"):
            hostfabric.run_fit(corpus, LDAConfig(n_topics=2), tmp_path,
                               n_hosts=2)
    assert not (tmp_path / "log").exists()      # nothing spawned
