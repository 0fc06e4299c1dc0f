"""The fit's programs built ahead (PR 38): `shard_corpus` reports its
plan before it deals a token, `ShardedGibbsLDA.fit` compiles `init_fn`
and the supersteps from it on a thread of its own and calls the
executables - the same state, bit for bit, as through `jax.jit`, which
every call the thread did not serve falls back to."""

import sys
import threading
import time

import numpy as np
import pytest

import jax

from onix.config import LDAConfig
from onix.corpus import synthetic_lda_corpus
from onix.parallel.mesh import make_mesh
from onix.parallel.sharded_gibbs import (ProgramsAhead, ShardedGibbsLDA,
                                         plan_of, shard_corpus)
from onix.utils import telemetry
from onix.utils.obs import counters
from tests.test_fit_setup import _digest

K = 4
BLOCK = 256


@pytest.fixture(scope="module")
def corpus():
    c, _, _ = synthetic_lda_corpus(n_docs=60, n_vocab=37, n_topics=K,
                                   mean_doc_len=30, alpha=0.2, eta=0.05,
                                   seed=5)
    return c


@pytest.fixture
def fresh():
    telemetry.reset_for_tests()
    counters.reset("fit")
    counters.reset("jit")


def _model(corpus, dp=1, mp=1, **kw):
    cfg = LDAConfig(**{"n_topics": K, "alpha": 0.5, "eta": 0.05,
                       "n_sweeps": 3, "burn_in": 1, "block_size": BLOCK,
                       "seed": 9, "superstep": 2, **kw})
    mesh = make_mesh(dp=dp, mp=mp, devices=jax.devices()[:dp * mp])
    return ShardedGibbsLDA(cfg, corpus.n_vocab, mesh=mesh)


def _through_jit(monkeypatch):
    """Every program's build raises: the fit runs as it did before it
    built anything ahead, through the jitted functions."""
    def refuse(self, plan, warm):
        raise RuntimeError("no abstract arguments today")
    monkeypatch.setattr(ShardedGibbsLDA, "_abstract_args", refuse)


def _same_fit(a, b):
    assert a["ll_history"] == b["ll_history"]
    for name, x, y in zip(a["state"]._fields, a["state"], b["state"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)
    np.testing.assert_array_equal(a["theta"], b["theta"])
    np.testing.assert_array_equal(a["phi_wk"], b["phi_wk"])


def _spans(name):
    return [s for s in telemetry.TRACER.spans() if s.name == name]


# -- the plan ---------------------------------------------------------------

# `shard_corpus`'s layouts on the parent commit (b091483) for the corpus
# above, seed 11.
PARENT_LAYOUTS = {
    (1, 1, 1): "e6f770d5e5567c06", (1, 1, 2): "95649b127e1732fa",
    (1, 2, 1): "98a29efa6b5da7a1", (1, 2, 2): "98a29efa6b5da7a1",
    (4, 1, 1): "7bc8b4f9ba505a57", (4, 1, 2): "7bc8b4f9ba505a57",
    (4, 2, 1): "55ee8adcfa75d732", (4, 2, 2): "55ee8adcfa75d732",
}


@pytest.mark.parametrize("n_data,n_mp,n_groups", sorted(PARENT_LAYOUTS))
def test_the_plan_is_the_layouts_shapes_and_the_layout_is_the_parents(
        corpus, n_data, n_mp, n_groups):
    plans = []
    sc = shard_corpus(corpus, n_data, BLOCK, seed=11, n_mp=n_mp,
                      n_groups=n_groups, on_plan=plans.append)
    (plan,) = plans
    assert plan == plan_of(sc, corpus.n_tokens)
    assert (plan.n_data, plan.n_mp, plan.nb, plan.block) \
        == sc.doc_blocks.shape == sc.word_blocks.shape \
        == sc.mask_blocks.shape
    assert (plan.n_data, plan.n_docs_local) == sc.doc_map.shape
    assert (plan.n_docs_local, plan.n_vocab, plan.n_vocab_local,
            plan.n_tokens) == (sc.n_docs_local, corpus.n_vocab,
                               sc.n_vocab_local, corpus.n_tokens)
    assert all(type(v) is int for v in plan)
    assert _digest(sc.doc_blocks, sc.word_blocks, sc.mask_blocks,
                   sc.doc_map) == PARENT_LAYOUTS[n_data, n_mp, n_groups]


def test_the_plan_comes_before_a_token_is_dealt(corpus, monkeypatch):
    """The hook runs before the generator that shuffles is drawn from
    (here it could not be: it is None)."""
    def stop(plan):
        raise KeyboardInterrupt(plan)
    made = []
    monkeypatch.setattr(np.random, "default_rng",
                        lambda seed: made.append(seed))
    with pytest.raises(KeyboardInterrupt):
        shard_corpus(corpus, 2, BLOCK, seed=11, on_plan=stop)
    assert made == [11]         # made, and not yet drawn from


# -- the fit through the executables ------------------------------------------

@pytest.mark.parametrize("warm", [False, True], ids=["cold", "init_phi"])
@pytest.mark.parametrize("dp,mp", [(1, 1), (4, 1), (2, 2)],
                         ids=["dp1_fast", "shard_map_dp4",
                              "shard_map_dp2_mp2"])
def test_fit_through_executables_is_the_jitted_fit(
        eight_devices, corpus, monkeypatch, fresh, dp, mp, warm):
    phi = (np.random.default_rng(2).gamma(0.3, size=(corpus.n_vocab, K))
           if warm else None)
    model = _model(corpus, dp, mp)
    assert model.dp1_fast is (dp * mp == 1)
    ahead = model.fit(corpus, init_phi=phi)
    # init_fn, the superstep of two sweeps with the first
    # log-likelihood, the tail of one without: three programs, each
    # called once.
    assert counters.get("fit.precompile.hit") == 3
    assert counters.get("fit.precompile.miss") == 0
    (built,) = _spans("fit.precompile")
    assert built.attrs == {"count": 3, "programs": [
        "init", "(2, True)", "(1, False)"]}
    waits = _spans("fit.compile_wait")
    assert [s.attrs["key"] for s in waits] == built.attrs["programs"]
    assert {s.attrs["program"] for s in waits} == {
        "init_fn", "superstep_dp1_fn" if dp * mp == 1 else "superstep_fn"}
    assert all(type(s.attrs["ready"]) is bool for s in waits)

    _through_jit(monkeypatch)
    jitted = _model(corpus, dp, mp).fit(corpus, init_phi=phi)
    assert counters.get("fit.precompile.hit") == 3
    assert counters.get("fit.precompile.miss") == 3
    assert _spans("fit.precompile")[-1].attrs["failed"] == [
        "init", "(2, True)", "(1, False)"]
    _same_fit(ahead, jitted)


def test_per_sweep_fit_calls_its_two_programs_again_and_again(
        corpus, monkeypatch, fresh):
    """The benchmark's path: a callback makes every segment one sweep."""
    seen = []
    ahead = _model(corpus).fit(corpus, n_sweeps=4,
                               callback=lambda s, st: seen.append(s))
    assert seen == [0, 1, 2, 3]
    assert _spans("fit.precompile")[0].attrs["programs"] == [
        "init", "(1, True)", "(1, False)"]
    assert counters.get("fit.precompile.hit") == 5
    assert counters.get("fit.precompile.miss") == 0
    assert len(_spans("fit.compile_wait")) == 3    # one a program
    _through_jit(monkeypatch)
    _same_fit(ahead, _model(corpus).fit(corpus, n_sweeps=4,
                                        callback=lambda s, st: None))


def test_compiles_hang_under_the_thread_that_made_them(corpus, fresh):
    model = _model(corpus)
    model.fit(corpus)
    by_id = {s.span_id: s for s in telemetry.TRACER.spans()}
    (built,) = _spans("fit.precompile")
    (prepare,) = _spans("fit.prepare")
    fits = [s for s in _spans("jit.compile")
            if s.attrs["program"] in ("init_fn", "superstep_dp1_fn")]
    assert [s.attrs["program"] for s in fits] == [
        "init_fn", "superstep_dp1_fn", "superstep_dp1_fn"]
    assert {s.parent_id for s in fits} == {built.span_id}
    for s in _spans("jit.compile"):
        asked = getattr(by_id.get(s.parent_id), "name", None)
        assert asked not in ("fit.superstep", "fit.compile_wait")
        if asked == "fit.init_state":       # the one-op programs around it
            assert s.attrs["program"] != "init_fn"
    # One trace: the thread runs in a copy of the fit's context, and
    # starts while the layout is still being made.
    assert built.parent_id == prepare.span_id
    assert built.trace_id == prepare.trace_id
    assert prepare.t0 <= built.t0 <= prepare.t0 + prepare.dur_s


def test_a_wrapper_over_prepare_gets_one_call_and_the_fit_builds_ahead(
        corpus, fresh):
    """The benchmark's drivers replace `model.prepare` with a function
    of the corpus alone that keeps the layout."""
    model = _model(corpus)
    kept, prepare = [], model.prepare

    def prepare_and_keep(c):
        kept.append(prepare(c))
        return kept[-1]

    model.prepare = prepare_and_keep
    fit = model.fit(corpus)
    assert len(kept) == 1 and fit["sharded_corpus"] is kept[0]
    assert counters.get("fit.precompile.hit") == 3
    assert counters.get("fit.precompile.miss") == 0
    assert len(_spans("fit.precompile")) == 1
    assert model._on_plan is None


def test_a_prepare_of_its_own_is_planned_for_afterwards(corpus, fresh):
    """A `prepare` that never reports a plan, and one that hands back
    another layout than it reported: the fit plans from the layout it
    got."""
    model = _model(corpus)
    sc = shard_corpus(corpus, 1, BLOCK, seed=9)
    model.prepare = lambda c: sc
    silent = model.fit(corpus)
    assert counters.get("fit.precompile.miss") == 0
    (built,) = _spans("fit.precompile")
    (prepare,) = _spans("fit.prepare")
    assert built.parent_id is None and built.t0 >= prepare.t0 + prepare.dur_s

    other = _model(corpus)
    narrow = shard_corpus(corpus, 1, BLOCK // 2, seed=9)
    plain = other.prepare
    other.prepare = lambda c: (plain(c), narrow)[1]
    got = other.fit(corpus)
    assert got["sharded_corpus"] is narrow
    assert got["state"].z.shape[-1] == BLOCK // 2
    assert counters.get("fit.precompile.miss") == 0
    assert len(_spans("fit.precompile")) == 3
    _same_fit(silent, _model(corpus).fit(corpus))


def test_a_restored_fit_builds_no_init_fn(corpus, tmp_path, fresh):
    _model(corpus, checkpoint_every=2).fit(corpus, n_sweeps=2,
                                          checkpoint_dir=tmp_path)
    telemetry.reset_for_tests()
    counters.reset("fit")
    resumed = _model(corpus, checkpoint_every=2).fit(
        corpus, n_sweeps=5, checkpoint_dir=tmp_path)
    (built,) = _spans("fit.precompile")
    # Sweeps 2-3 to the next checkpoint, then the tail: the first
    # segment of a fit carries the log-likelihood it starts from.
    assert built.attrs["programs"] == ["(2, True)", "(1, False)"]
    assert counters.get("fit.precompile.hit") == 2
    assert counters.get("fit.precompile.miss") == 0
    assert [s for s, _ in resumed["ll_history"]] == [1, 3, 4]
    whole = _model(corpus, checkpoint_every=2).fit(corpus, n_sweeps=5)
    for x, y in zip(resumed["state"], whole["state"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_nothing_of_the_thread_outlives_a_fit_that_raises(corpus, fresh):
    class Stop(Exception):
        pass

    def stop(sweep, state):
        raise Stop

    model = _model(corpus, n_sweeps=6)
    with pytest.raises(Stop):
        model.fit(corpus, callback=stop)
    assert model._on_plan is None
    assert not [t for t in threading.enumerate()
                if t.name == "onix-fit-precompile"]


# -- the fallbacks, one at a time ---------------------------------------------

def _double():
    return jax.jit(lambda x, *, times: x * times, static_argnames="times")


def test_an_unplanned_program_goes_through_jit(fresh):
    double = _double()
    ahead = ProgramsAhead([], lambda key: None)
    try:
        out = ahead.call(("double", 2), double, np.arange(3), times=2)
    finally:
        ahead.close()
    np.testing.assert_array_equal(out, [0, 2, 4])
    assert counters.get("fit.precompile.miss") == 1
    assert counters.get("fit.precompile.hit") == 0
    assert not _spans("fit.compile_wait")


def test_a_build_that_raises_and_one_for_other_shapes_go_through_jit(fresh):
    double = _double()

    def build(key):
        if key == "raises":
            raise RuntimeError("no compiler today")
        return double.lower(jax.ShapeDtypeStruct((3,), np.int32),
                            times=2).compile()

    ahead = ProgramsAhead(["raises", "short"], build)
    three, five = np.arange(3, dtype=np.int32), np.arange(5, dtype=np.int32)
    tally = lambda: (counters.get("fit.precompile.hit"),
                     counters.get("fit.precompile.miss"))
    try:
        a = ahead.call("raises", double, three, times=2)
        assert tally() == (0, 1)
        b = ahead.call("short", double, three, times=2)
        assert tally() == (1, 1)
        # Five elements where three were planned: refused by the
        # executable, served by jit, and not asked of it again.
        c = ahead.call("short", double, five, times=2)
        d = ahead.call("short", double, three, times=2)
        assert tally() == (1, 3)
    finally:
        ahead.close()
    for got in (a, b, d):
        np.testing.assert_array_equal(got, [0, 2, 4])
    np.testing.assert_array_equal(c, [0, 2, 4, 6, 8])
    (built,) = _spans("fit.precompile")
    assert built.attrs == {"count": 2, "programs": ["raises", "short"],
                           "failed": ["raises"]}
    assert [s.attrs["key"] for s in _spans("fit.compile_wait")] == [
        "raises", "short"]


def test_a_program_not_begun_is_dropped_at_close(fresh):
    begun, go_on, built = threading.Event(), threading.Event(), []

    def build(key):
        built.append(key)
        begun.set()
        assert go_on.wait(timeout=30)

    ahead = ProgramsAhead(["first", "second"], build)
    assert begun.wait(timeout=30)
    closer = threading.Thread(target=ahead.close)
    closer.start()
    while not ahead._closed:
        time.sleep(0.001)
    go_on.set()
    closer.join(timeout=30)
    assert not closer.is_alive() and not ahead._thread.is_alive()
    assert built == ["first"]
    # Nobody is left waiting on what was dropped.
    assert all(fut.done() for fut in ahead._futures.values())
    assert isinstance(ahead._futures["second"].exception(), RuntimeError)


def test_fits_side_by_side_each_keep_their_own_programs(eight_devices,
                                                        corpus, fresh):
    """More fits at once than this machine has cores for, the
    interpreter switching threads as often as it can: every fit gets
    its own executables and leaves its own seed's state."""
    want = {seed: _model(corpus, seed=seed).fit(corpus) for seed in (1, 2)}
    hits = counters.get("fit.precompile.hit")
    got, errors = {}, []

    def one(i, seed):
        try:
            got[i] = (seed, _model(corpus, seed=seed).fit(corpus))
        except BaseException as e:      # read below, on the test's thread
            errors.append(e)
            raise

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=one, args=(i, 1 + i % 2))
                   for i in range(6)]
        deadline = time.monotonic() + 240
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not [t for t in threads if t.is_alive()]
    assert len(got) == 6
    for seed, fit in got.values():
        _same_fit(fit, want[seed])
    assert counters.get("fit.precompile.hit") == hits + 18
    assert counters.get("fit.precompile.miss") == 0


def test_hostfabrics_calls_outside_a_fit_are_plain(corpus, fresh):
    """`prepare` and `init_state` on their own (hostfabric's workers)
    report no plan and count nothing."""
    model = _model(corpus)
    sc = model.prepare(corpus)
    model.init_state(sc)
    assert plan_of(sc, corpus.n_tokens).nb == sc.doc_blocks.shape[2]
    assert not counters.snapshot("fit.")
    assert not _spans("fit.precompile") and not _spans("fit.compile_wait")
