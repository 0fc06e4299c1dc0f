"""One shim, for one test this directory's PR may not edit.

`test_benchmark_readers.py::test_program_span_gives_nothing_once_the_
ring_has_wrapped` (PR 26) reaches the span drop by shrinking the flight
ring, from when spans and counter deltas shared it. Since PR 37 closed
spans live in a store of their own, whose size no setting of the ring
moves (`telemetry.SPAN_STORE`), so for that test alone a shrunk ring
shrinks the store with it - what the test would do itself, as
`tests/test_telemetry.py` does. A `benchmark` PR that repairs the test
deletes this file."""

import collections

import pytest

from onix.utils import telemetry


@pytest.fixture(autouse=True)
def _span_store_follows_the_ring(request, monkeypatch):
    if request.node.name != \
            "test_program_span_gives_nothing_once_the_ring_has_wrapped":
        yield
        return
    recorder = telemetry.RECORDER
    store = recorder._spans
    reconfigure = telemetry.FlightRecorder.reconfigure

    def shrink_both(self, capacity=None, out_dir=None):
        reconfigure(self, capacity=capacity, out_dir=out_dir)
        if capacity is not None:
            self._spans = collections.deque(self._spans, maxlen=capacity)
    monkeypatch.setattr(telemetry.FlightRecorder, "reconfigure", shrink_both)
    yield
    recorder._spans = store
    store.clear()
