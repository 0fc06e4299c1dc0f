"""Test bootstrap: force an 8-device virtual CPU mesh before JAX imports.

The reference had no way to test distributed behavior without a real
cluster (SURVEY.md §4 "Multi-node without a cluster: not solved by the
reference"). onix tests every sharded path on fake devices
(SURVEY.md §4.3).
"""

import os

# Force CPU: tier-1 never touches an accelerator, wherever it is launched
# (on a machine with a chip JAX would otherwise take it by default). Set
# both the env (subprocesses the tests start inherit it) and, below, the
# live jax config. ONIX_TPU_TESTS=1 keeps the ambient backend instead —
# the explicit opt-in for the `tpu`-marked tests:
#   ONIX_TPU_TESTS=1 python -m pytest -m tpu      (on the chip)
_TPU_OPT_IN = os.environ.get("ONIX_TPU_TESTS") == "1"   # 0/unset = off,
#                             matching every other 0/1 knob in the repo
if not _TPU_OPT_IN:
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if not _TPU_OPT_IN:
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Auto-skip `tpu`-marked tests off-TPU — THE mechanism for
    accelerator-gated tests (registered in pyproject.toml): mark the
    test, never hand-roll a backend check. The suite forces CPU above,
    so these run only when launched against a real device explicitly
    (ONIX_TPU_TESTS=1 python -m pytest -m tpu)."""
    backend = jax.default_backend()
    if backend != "tpu":
        skip_tpu = pytest.mark.skip(
            reason=f"needs a real TPU backend (default backend: "
                   f"{backend}); run on the chip with ONIX_TPU_TESTS=1 "
                   "python -m pytest -m tpu")
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip_tpu)
    # `multihost`-marked tests are the HEAVY fit-fabric runs (many
    # worker processes, real wall-clock); same opt-in discipline as
    # `tpu`, keyed on ONIX_MULTIHOST_TESTS=1. The 2-worker chaos smoke
    # in tests/test_hostfabric.py is deliberately UNMARKED — the
    # SIGKILL-quarantine-resume contract is tier-1.
    if os.environ.get("ONIX_MULTIHOST_TESTS") != "1":
        skip_mh = pytest.mark.skip(
            reason="heavy multi-process fabric test; opt in with "
                   "ONIX_MULTIHOST_TESTS=1")
        for item in items:
            if "multihost" in item.keywords:
                item.add_marker(skip_mh)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Flight-recorder trigger (r18, docs/OBSERVABILITY.md): a FAILED
    `faults`-marker test dumps the telemetry ring — the span closes,
    counter deltas, and fault firings leading up to the assertion — so
    every chaos failure ships its own postmortem artifact. Routed via
    ONIX_TELEMETRY_DIR (or telemetry.recorder_dir if the test applied
    a config); unrouted dumps are counted, not written."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when == "call" and rep.failed and "faults" in item.keywords:
        from onix.utils import telemetry
        path = telemetry.RECORDER.dump(f"chaos-test-failed-{item.name}")
        if path is not None:
            item.add_report_section(
                "call", "flight-recorder", f"postmortem dumped to {path}")


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
