"""What the chip runs has a name, and what the host waits for has a span.

- ``TimeLine.span``: one ring event per span with ``dur_ns``, ``id``,
  ``parent`` and ``job``; a thread-local stack; closed by an exception.
- a GBM ``train()`` leaves ``job.run > train.bin, train.block.launch,
  train.block.absorb (> pull, score), train.final_metrics`` under one job;
  ``train.block.score`` says where its F came from (``source``).
- the lowered tree program names every ``h2o.tree.*`` scope (both
  engines, both binnings); scoring names ``h2o.score.descent``; binning
  names ``h2o.bin.*``.
- ``DispatchStats.compile_seconds()`` keeps the durations jax hands the
  listener; the OOM ladder leaves ``safety.*`` point events.
- every program JAX makes ready leaves one ``DispatchStats.programs()``
  record, which is also an ``exec.ready`` span: its seconds are
  ``compile_seconds()``' addends, its ``parent`` the span that asked.
"""

import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.core.diag import DispatchStats, TimeLine
from h2o_tpu.core.frame import Frame, Vec, T_CAT

_ADDENDS = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
            "backend_compile_duration")


def _spans(kind=None):
    return [e for e in TimeLine.snapshot()
            if "dur_ns" in e and (kind is None or e["kind"] == kind)]


# ------------------------------------------------------------------ spans

def test_span_is_one_event_with_parent_id_and_job():
    TimeLine.clear()
    with TimeLine.span("t", "root", job="job_1", note="x"):
        with TimeLine.span("t", "child"):
            with TimeLine.span("t", "leaf"):
                pass
        with TimeLine.span("t", "second"):
            pass
    ev = {e["what"]: e for e in _spans("t")}
    assert len(_spans("t")) == 4 and set(ev) == {"root", "child", "leaf",
                                                 "second"}
    root = ev["root"]
    assert root["parent"] is None and root["note"] == "x"
    assert ev["child"]["parent"] == root["id"]
    assert ev["leaf"]["parent"] == ev["child"]["id"]
    assert ev["second"]["parent"] == root["id"]
    assert len({e["id"] for e in ev.values()}) == 4
    # the root's job is inherited all the way down
    assert {e["job"] for e in ev.values()} == {"job_1"}
    for e in ev.values():
        assert e["dur_ns"] >= 0 and e["ns"] > 0
        assert e["thread"] == threading.get_ident()
    # a parent lasts at least as long as its child, and closes after it
    assert root["dur_ns"] >= ev["child"]["dur_ns"] >= ev["leaf"]["dur_ns"]
    order = [e["what"] for e in _spans("t")]
    assert order == ["leaf", "child", "second", "root"]


def test_span_outside_any_job_has_no_job():
    TimeLine.clear()
    with TimeLine.span("t", "alone"):
        pass
    (e,) = _spans("t")
    assert e["job"] is None and e["parent"] is None


def test_exception_closes_the_span_and_unwinds_the_stack():
    TimeLine.clear()
    with pytest.raises(ValueError):
        with TimeLine.span("t", "outer", job="j"):
            with TimeLine.span("t", "boom"):
                raise ValueError("inside")
    assert [e["what"] for e in _spans("t")] == ["boom", "outer"]
    # nothing is left open on this thread
    with TimeLine.span("t", "after"):
        pass
    after = _spans("t")[-1]
    assert after["parent"] is None and after["job"] is None


def test_point_events_are_unchanged():
    TimeLine.clear()
    with TimeLine.span("t", "around", job="j"):
        TimeLine.record("t", "point", x=1)
    point = next(e for e in TimeLine.snapshot() if e["what"] == "point")
    assert set(point) == {"ns", "kind", "what", "thread", "x"}


def test_two_job_threads_do_not_share_a_stack():
    TimeLine.clear()
    inside = threading.Barrier(2, timeout=30)

    def work(name):
        with TimeLine.span("t", "root", job=name):
            inside.wait()               # both roots are open at once
            with TimeLine.span("t", "child"):
                inside.wait()

    threads = [threading.Thread(target=work, args=(f"job_{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    roots = {e["job"]: e for e in _spans("t") if e["what"] == "root"}
    kids = [e for e in _spans("t") if e["what"] == "child"]
    assert set(roots) == {"job_0", "job_1"} and len(kids) == 2
    for k in kids:
        # each child hangs under the root of its OWN thread and job
        assert k["parent"] == roots[k["job"]]["id"]
        assert k["thread"] == roots[k["job"]]["thread"]
    assert kids[0]["thread"] != kids[1]["thread"]


def test_span_lies_in_a_profile_as_trace_annotation(monkeypatch):
    """The block runs under ``TraceAnnotation("h2o:<kind>.<what>")``,
    which takes the span's ``info`` as the event's stats."""
    from h2o_tpu.core import diag
    seen = []

    class Spy:
        def __init__(self, name, **stats):
            seen.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(diag, "TraceAnnotation", Spy)
    with TimeLine.span("train", "block.pull"):
        pass
    with TimeLine.span("train", "final_metrics", source="carried_F"):
        pass
    with TimeLine.span("train", "block.score", source="descent"):
        pass
    assert seen == [("h2o:train.block.pull", {}),
                    ("h2o:train.final_metrics", {"source": "carried_F"}),
                    ("h2o:train.block.score", {"source": "descent"})]


# ------------------------------------------------------- a training's tree

def _toy_frame(rng, n=600, c=4):
    X = rng.normal(size=(n, c)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + 0.5 * rng.normal(size=n) > 0).astype(np.int32)
    names = [f"x{j}" for j in range(c)] + ["y"]
    vecs = [Vec(X[:, j]) for j in range(c)] + \
        [Vec(y, T_CAT, domain=["no", "yes"])]
    return Frame(names, vecs)


@pytest.mark.parametrize("validation, source", [(False, "carried_F"),
                                                (True, "descent")])
def test_gbm_train_leaves_one_tree_of_spans(cl, rng, validation, source):
    from h2o_tpu.models.tree.gbm import GBM
    fr = _toy_frame(rng)
    TimeLine.clear()
    GBM(ntrees=3, max_depth=2, seed=3, score_tree_interval=1).train(
        y="y", training_frame=fr,
        validation_frame=_toy_frame(rng, n=200) if validation else None)
    spans = _spans()
    roots = [e for e in spans if (e["kind"], e["what"]) == ("job", "run")]
    assert len(roots) == 1
    root = roots[0]
    mine = [e for e in spans if e["job"] == root["job"]]
    assert root["job"].startswith("job_") and root["parent"] is None

    def of(what):
        return [e for e in mine if e["kind"] == "train"
                and e["what"] == what]

    for what, n in (("bin", 1), ("block.launch", 3), ("block.absorb", 3),
                    ("block.pull", 3), ("block.score", 3),
                    ("final_metrics", 1)):
        assert len(of(what)) == n, (what, [e["what"] for e in mine])
    for what in ("bin", "block.launch", "block.absorb", "final_metrics"):
        assert {e["parent"] for e in of(what)} == {root["id"]}
    absorbs = {e["id"] for e in of("block.absorb")}
    assert {e["parent"] for e in of("block.pull")} == absorbs
    assert {e["parent"] for e in of("block.score")} == absorbs
    # the training frame's scorer reads the block's carried F; only a
    # validation frame's descends the block's trees
    assert {e["source"] for e in of("block.score")} == {source}
    assert not of("block.checkpoint")       # no recovery attached
    # children lie inside the root on the wall clock
    for e in mine:
        assert e["ns"] >= root["ns"]
        assert e["dur_ns"] <= root["dur_ns"]
    # the point events the overlap test reads are still there, in order
    marks = [e["what"] for e in TimeLine.snapshot()
             if e["what"].startswith("tree_block_")]
    assert marks.count("tree_block_launch") == 3
    assert marks.count("tree_block_materialize") == 3
    assert marks.index("tree_block_launch") < \
        marks.index("tree_block_materialize")


def test_timeline_route_serves_spans(cl):
    from h2o_tpu.api.handlers import timeline
    TimeLine.clear()
    with TimeLine.span("job", "run", job="job_x"):
        with TimeLine.span("train", "bin"):
            pass
    events = timeline({})["events"]
    by = {e["what"]: e for e in events}
    assert by["bin"]["parent"] == by["run"]["id"]
    assert by["bin"]["job"] == "job_x" and by["bin"]["dur_ns"] >= 0


# ---------------------------------------------------------- device scopes

TREE_SCOPES = ("h2o.tree.stats", "h2o.tree.hist.onehot",
               "h2o.tree.hist.contract", "h2o.coll.hist.table",
               "h2o.tree.split", "h2o.tree.route", "h2o.tree.predict")


def _scopes_in(lowered):
    """The ``h2o.*`` components of every location the lowered program
    carries."""
    text = lowered.as_text(debug_info=True)
    return set(re.findall(r"h2o\.[a-z_.]+[a-z]", text))


@pytest.mark.parametrize("kleaves", [0, 2], ids=["dense", "frontier"])
@pytest.mark.parametrize("adaptive", [False, True],
                         ids=["quantile", "adaptive"])
def test_tree_program_names_every_scope(cl, kleaves, adaptive):
    from h2o_tpu.models.tree import jit_engine as je
    R, C, B = 64, 3, 4
    F = 8 if adaptive else B
    fn = jax.jit(je._train_forest_impl, static_argnames=je._TF_STATIC)
    lowered = fn.lower(
        jnp.zeros((R, C), jnp.int32), jnp.zeros((R,), jnp.float32),
        jnp.ones((R,), jnp.float32), jnp.ones((R,), bool),
        jnp.zeros((R, 1), jnp.float32), jnp.zeros((C,), bool),
        jax.random.key(0), dist_name="bernoulli", K=1, ntrees=2,
        max_depth=3, nbins=B, k_cols=C, newton=True, sample_rate=1.0,
        learn_rate=0.1, learn_rate_annealing=1.0, min_rows=1.0,
        min_split_improvement=1e-5, block_rows=16, kleaves=kleaves,
        adaptive=adaptive, fine_nbins=F, sibling=True)
    have = _scopes_in(lowered)
    assert set(TREE_SCOPES) <= have, set(TREE_SCOPES) - have
    # nothing of the score or binning layers leaks into the tree program
    assert not {s for s in have if s.startswith(("h2o.score", "h2o.bin"))}


def test_scoring_and_binning_programs_name_their_scopes(cl):
    from h2o_tpu.models import metrics
    from h2o_tpu.models.tree import driver, shared_tree as st
    R, C, H, B = 32, 3, 7, 4
    score = st.forest_score.lower(
        jnp.zeros((R, C), jnp.int32), jnp.full((2, 1, H), -1, jnp.int32),
        jnp.zeros((2, 1, H, B + 1), bool), jnp.zeros((2, 1, H)), depth=2)
    assert _scopes_in(score) == {"h2o.score.descent"}
    values = st.forest_tree_values.lower(
        jnp.zeros((R, C), jnp.int32), jnp.full((2, 1, H), -1, jnp.int32),
        jnp.zeros((2, 1, H, B + 1), bool), jnp.zeros((2, 1, H)), depth=2)
    assert _scopes_in(values) == {"h2o.score.descent"}
    m = jnp.zeros((R, C), jnp.float32)
    # the split points and the metric kernel reduce across shards through
    # the named collectives, nothing else
    assert _scopes_in(st._quantile_split_points.lower(
        m, jnp.int32(R), nbins=B, mesh=cl.mesh)) == {
            "h2o.bin.quantile", "h2o.coll.quantile.count",
            "h2o.coll.quantile.range", "h2o.coll.quantile.rank"}
    assert _scopes_in(st._col_min_max.lower(m, jnp.int32(R))) == \
        {"h2o.bin.quantile"}
    assert _scopes_in(st._bin_all.lower(
        m, jnp.zeros((C, B - 1)), jnp.zeros((C,), bool), nbins=B)) == \
        {"h2o.bin.assign"}
    assert _scopes_in(driver._accum.lower(m, m)) == {"h2o.score.metrics"}
    p = jnp.zeros((R,))
    assert _scopes_in(metrics._binomial_kernel.lower(
        p, p, p, p > 0, mesh=cl.mesh)) == {
            "h2o.score.metrics", "h2o.coll.score.hist",
            "h2o.coll.score.sums"}


# --------------------------------------------------------------- counters

def test_compile_seconds_grow_by_a_compile_not_by_a_cached_call(cl):
    DispatchStats.install_xla_listener()

    def total():
        return sum(DispatchStats.compile_seconds().values())

    f = jax.jit(lambda x: jnp.cos(x) * 3.25 + 1.5)
    x = jnp.arange(7.0)
    before, n0 = total(), DispatchStats.xla_compiles()
    f(x).block_until_ready()
    after = total()
    assert after > before
    assert DispatchStats.xla_compiles() == n0 + 1
    secs = DispatchStats.compile_seconds()
    assert {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
            "backend_compile_duration"} <= set(secs)
    assert all(v >= 0 for v in secs.values())
    f(x).block_until_ready()                  # cached: nothing to add
    assert total() == after
    assert DispatchStats.snapshot()["compile_seconds"] == secs


def test_oom_ladder_retry_leaves_a_safety_event(cl):
    from h2o_tpu.core import chaos, oom
    TimeLine.clear()
    chaos.configure(oom_transient=1, seed=0)
    try:
        assert oom.oom_ladder("t.trace", lambda: "device") == "device"
    finally:
        chaos.reset()
    safety = [(e["what"], e["site"]) for e in TimeLine.snapshot()
              if e["kind"] == "safety"]
    assert ("oom_events", "t.trace") in safety
    assert ("sweeps", "t.trace") in safety
    assert oom.stats()["sites"]["t.trace"]["oom_events"] == 1


def test_compile_cache_key_holds_the_names_not_the_directory(cl):
    """The persistent cache strips locations from its key by default, so
    a program that differs only in its scopes would load the older
    executable and profiles would show stale names.  The package keys
    on the metadata, with file names relative to the checkout."""
    from h2o_tpu.core import cloud
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    pattern = jax.config.jax_hlo_source_file_canonicalization_regex
    assert re.sub(pattern, "", cloud.__file__) == "h2o_tpu/core/cloud.py"
    # a file outside the checkout keeps its name
    assert re.sub(pattern, "", re.__file__) == re.__file__


# ---------------------------------------------------- programs made ready

def _mark():
    """A point to count from: every record made after it has a larger
    ``id``; and the summed seconds of ``compile_seconds()``' addends."""
    DispatchStats.install_xla_listener()
    return TimeLine.new_id(), _ready_seconds()


def _ready_seconds():
    secs = DispatchStats.compile_seconds()
    return sum(secs.get(k, 0.0) for k in _ADDENDS)


def _made_since(mark):
    """This thread's records since ``mark``."""
    return [p for p in DispatchStats.programs() if p["id"] > mark
            and p["thread"] == threading.get_ident()]


def _vec(n):
    # a device array that no program made
    return jax.device_put(np.arange(n, dtype=np.float32))


def _jit_once():
    f, x = jax.jit(lambda x: jnp.sin(x) * 1.375 + 0.625), _vec(11)
    return lambda: f(x).block_until_ready(), ["jit(<lambda>)"]


def _called_again():
    f, x = jax.jit(lambda x: jnp.sin(x) * 1.125 - 0.375), _vec(11)
    f(x).block_until_ready()
    return lambda: f(x).block_until_ready(), []


def _eager_op():
    x = jax.device_put(np.ones((7, 13, 19), np.float32))
    return lambda: (x * 3.0).block_until_ready(), ["jit(multiply)"]


def _nested_jit():
    inner = jax.jit(lambda x: x * 2.75)

    @jax.jit
    def outer(x):
        return inner(x).sum() + inner(x + 1.0).sum() + jnp.sum(x)
    x = _vec(9)
    return lambda: outer(x).block_until_ready(), ["jit(outer)"]


@pytest.mark.parametrize("case", [_jit_once, _called_again, _eager_op,
                                  _nested_jit],
                         ids=["jit_once", "called_again", "eager_op",
                              "nested_jit"])
def test_each_program_made_ready_leaves_one_record(cl, case):
    from jax._src import monitoring
    run, funs = case()
    traces = []

    def raw(event, duration, **kw):
        if event.endswith("/jaxpr_trace_duration"):
            traces.append(duration)

    mark, secs0 = _mark()
    n0 = DispatchStats.xla_compiles()
    monitoring.register_event_duration_secs_listener(raw)
    try:
        run()
    finally:
        monitoring.unregister_event_duration_listener(raw)
    made = _made_since(mark)
    assert [p["fun"] for p in made] == funs
    assert DispatchStats.xla_compiles() - n0 >= len(funs)
    # the records' seconds are what compile_seconds() grew by
    assert sum(p["trace_s"] + p["lower_s"] + p["compile_s"]
               for p in made) == pytest.approx(_ready_seconds() - secs0,
                                                abs=1e-9)
    for p in made:
        assert p["trace_s"] > 0 and p["lower_s"] > 0 and p["compile_s"] > 0
        assert p["cache"] in ("hit", "compiled", "uncached")
        assert p["trace_s"] + p["lower_s"] + p["compile_s"] <= \
            p["dur_ns"] / 1e9 + 1e-6
    if case is _nested_jit:
        # the inner traces fired events of their own, which the outer
        # trace holds: counted once
        assert len(traces) > 1 and made[0]["trace_s"] < sum(traces)
    # each record is also a span of the ring
    ring = {e["id"]: e for e in TimeLine.snapshot()
            if (e["kind"], e["what"]) == ("exec", "ready")}
    for p in made:
        assert ring[p["id"]] == p


def test_a_program_records_the_span_that_asked_for_it(cl):
    f, x = jax.jit(lambda x: jnp.cos(x) * 0.8125), _vec(13)
    mark, _ = _mark()
    with TimeLine.span("job", "run", job="job_ready"):
        with TimeLine.span("t", "ask") as ask:
            f(x).block_until_ready()
    rec, = _made_since(mark)
    assert rec["parent"] == ask["id"] and rec["job"] == "job_ready"
    assert rec["thread"] == ask["thread"]
    # on the ring's clock it lies inside the span that asked
    assert ask["ns"] - 1000 <= rec["ns"]
    assert rec["ns"] + rec["dur_ns"] <= ask["ns"] + ask["dur_ns"] + 1000


def test_persistent_cache_reads_compiled_then_hit(cl, tmp_path):
    from jax._src import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    f, x = (lambda x: jnp.tanh(x) * 1.0625 - 0.25), _vec(17)
    mark, _ = _mark()
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        compilation_cache.reset_cache()
        # one call line for both: the cache key holds the caller's
        # location; clear_caches() is a new process, as far as the
        # program can tell
        for _ in range(2):
            jax.jit(f)(x).block_until_ready()
            jax.clear_caches()
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    first, again = [p for p in _made_since(mark)
                    if p["fun"] == "jit(<lambda>)"]
    assert first["cache"] == "compiled" and first["retrieve_s"] is None
    assert again["cache"] == "hit"
    assert 0 < again["retrieve_s"] <= again["compile_s"]


def test_gbm_train_records_its_programs_under_the_spans_that_asked(cl, rng):
    from h2o_tpu.models.tree.gbm import GBM
    fr = _toy_frame(rng, n=520, c=7)
    DispatchStats.install_xla_listener()
    jax.clear_caches()              # the block program is traced again
    TimeLine.clear()
    GBM(ntrees=2, max_depth=3, seed=5, score_tree_interval=1).train(
        y="y", training_frame=fr)
    spans = _spans()
    assert not [e for e in spans if (e["kind"], e["what"]) ==
                ("exec", "compile")]
    root, = [e for e in spans if (e["kind"], e["what"]) == ("job", "run")]
    launches = {e["id"] for e in spans
                if (e["kind"], e["what"]) == ("train", "block.launch")}
    blocks = [e for e in spans if (e["kind"], e["what"]) == ("exec", "ready")
              and e["fun"] == "jit(_train_forest_impl)"]
    assert blocks
    assert {e["parent"] for e in blocks} <= launches
    assert {e["job"] for e in blocks} == {root["job"]}
