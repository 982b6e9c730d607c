"""Set-up from the inside (ISSUE 36): the process-wide recorder, a span's
parent, the program's ``startup/*`` spans where the work happens, and
JAX's own trace / lower / compile-or-cache-load events as ``compile/*``
spans on the same recorder and clock.

The process-wide recorder is shared by every test of a worker, so each
test reads only what closed after its own mark (``records(since_seq=...)``)
or what carries its own function's name.
"""
import inspect
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from byol_tpu.core import preflight
from byol_tpu.observability import goodput as goodput_lib
from byol_tpu.observability import spans as spans_lib

BUILD_PARTS = ["startup/build/net", "startup/build/init",
               "startup/build/remat_tags", "startup/build/optimizer",
               "startup/build/state", "startup/build/place",
               "startup/build/jit"]


def _tiny_rcfg_and_mesh():
    from byol_tpu.cli import build_parser, config_from_args
    from byol_tpu.core.config import resolve
    from byol_tpu.parallel.mesh import MeshSpec, build_mesh
    cfg = config_from_args(build_parser().parse_args(
        "--task fake --arch resnet18 --image-size-override 16 --batch-size "
        "16 --epochs 2 --warmup 1 --head-latent-size 32 --projection-size "
        "16 --no-half".split()))
    mesh = build_mesh(MeshSpec(data=8))
    rcfg = resolve(cfg, num_train_samples=64, num_test_samples=16,
                   output_size=10, input_shape=(16, 16, 3))
    return rcfg, mesh


def _build(rcfg, mesh):
    """As the trainer and the benchmark's drivers call it: with the plan."""
    from byol_tpu.parallel.compile_plan import build_plan
    from byol_tpu.training.build import setup_training
    plan = build_plan(mesh)
    return setup_training(rcfg, mesh, jax.random.PRNGKey(0), plan=plan)


# ---------------------------------------------------------------------------
# the recorder: parent, seq at open, add()
# ---------------------------------------------------------------------------

class TestParentAndSeq:
    def test_parent_is_the_span_open_on_the_thread(self):
        rec = spans_lib.SpanRecorder()
        with rec.span("outer"):
            with rec.span("first"):
                with rec.span("leaf"):
                    pass
            with rec.span("second"):
                pass
        with rec.span("next"):
            pass
        by = {r.name: r for r in rec.records()}
        assert by["outer"].parent == -1 and by["next"].parent == -1
        assert by["first"].parent == by["outer"].seq
        assert by["second"].parent == by["outer"].seq
        assert by["leaf"].parent == by["first"].seq
        # seq is taken at OPEN: a parent's is lower than its children's
        assert [by[n].seq for n in ("outer", "first", "leaf", "second",
                                    "next")] == [0, 1, 2, 3, 4]

    def test_parent_is_per_thread(self):
        rec = spans_lib.SpanRecorder()

        def worker():
            with rec.span("thread/top"):
                pass

        with rec.span("main/outer"):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
        by = {r.name: r for r in rec.records()}
        assert by["thread/top"].parent == -1

    def test_records_since_seq_is_a_place_in_closing_order(self):
        """``seq`` at open keeps the cursor's contract: everything that
        CLOSED after the marked span, a parent with a lower ``seq``
        included — a goodput window must not lose the span that was open
        across its fold."""
        rec = spans_lib.SpanRecorder()
        with rec.span("outer"):
            with rec.span("a"):
                pass
            mark = rec.last_seq()               # a's: outer is still open
            with rec.span("b"):
                pass
        assert [r.name for r in rec.records(since_seq=mark)] == ["b", "outer"]
        assert rec.records(since_seq=rec.last_seq()) == []
        # a mark the ring has dropped: everything retained is newer
        small = spans_lib.SpanRecorder(capacity=2)
        for name in "xyz":
            with small.span(name):
                pass
        assert [r.name for r in small.records(since_seq=0)] == ["y", "z"]

    def test_note_adds_attrs_while_open(self):
        rec = spans_lib.SpanRecorder()
        with rec.span("startup/build/init", batch=2) as s:
            s.note(leaves=3)
        assert rec.records()[0].attrs == {"batch": 2, "leaves": 3}

    def test_add_takes_the_open_span_as_parent_and_adopts_what_ran_inside(
            self):
        rec = spans_lib.SpanRecorder()
        with rec.span("startup/build"):
            t0 = time.perf_counter()
            with rec.span("inner/real"):
                with rec.span("inner/leaf"):
                    pass
            rec.add("compile/trace", time.perf_counter() - 1e-7,
                    time.perf_counter(), fun="callee")   # ended first
            rec.add("compile/trace", t0, time.perf_counter(), fun="caller")
        by = {(r.name, (r.attrs or {}).get("fun")): r
              for r in rec.records()}
        build = by["startup/build", None]
        caller = by["compile/trace", "caller"]
        callee = by["compile/trace", "callee"]
        assert caller.parent == build.seq and caller.depth == 1
        assert callee.parent == caller.seq and callee.depth == 2
        assert by["inner/real", None].parent == caller.seq
        assert by["inner/real", None].depth == 2
        leaf = by["inner/leaf", None]
        assert leaf.parent == by["inner/real", None].seq and leaf.depth == 3
        # ... so self time can be read from the ring
        own = goodput_lib.self_seconds(rec.records())
        assert own[caller.seq] == pytest.approx(
            caller.seconds - callee.seconds - by["inner/real", None].seconds)
        assert own[build.seq] == pytest.approx(build.seconds
                                               - caller.seconds)

    def test_self_seconds_takes_overlapping_children_off_once(self):
        S = spans_lib.Span
        ring = [S("child", 1.0, 3.0, 0, 1, 1, 0, None),
                S("child", 2.0, 4.0, 0, 1, 2, 0, None),
                S("spills", 9.0, 12.0, 0, 1, 3, 0, None),
                S("parent", 0.0, 10.0, 0, 0, 0, -1, None)]
        own = goodput_lib.self_seconds(ring)
        assert own[0] == pytest.approx(10.0 - 3.0 - 1.0)
        assert own[1] == 2.0 and own[3] == 3.0

    def test_chrome_export_carries_seq_and_parent(self, tmp_path):
        rec = spans_lib.SpanRecorder()
        with rec.span("outer"):
            with rec.span("inner", step=1):
                pass
        path = str(tmp_path / "trace.json")
        spans_lib.export_chrome_trace(rec.records(), path)
        with open(path) as f:
            xs = {e["name"]: e["args"] for e in json.load(f)["traceEvents"]
                  if e["ph"] == "X"}
        assert xs["outer"] == {"seq": 0, "parent": -1}
        assert xs["inner"] == {"seq": 1, "parent": 0, "step": 1}


# ---------------------------------------------------------------------------
# the clock: JAX's epoch stamps through the anchor
# ---------------------------------------------------------------------------

def test_epoch_round_trip_and_added_span_meets_a_perf_counter_span():
    t = time.perf_counter()
    assert spans_lib.from_epoch(spans_lib.epoch_ns(t) / 1e9) == \
        pytest.approx(t, abs=2e-6)
    rec = spans_lib.SpanRecorder()
    with rec.span("around"):
        e0 = time.time()
        time.sleep(0.01)
        e1 = time.time()
    rec.add("timed/elsewhere", spans_lib.from_epoch(e0),
            spans_lib.from_epoch(e1))
    around, added = rec.records()
    # the two host clocks agree to well under a millisecond here
    assert abs(added.t0 - around.t0) < 1e-3
    assert abs(added.t1 - around.t1) < 1e-3
    assert added.seconds == pytest.approx(e1 - e0, abs=1e-6)


# ---------------------------------------------------------------------------
# JAX's compile events on the process's recorder
# ---------------------------------------------------------------------------

def test_place_compile_cache_installs_one_set_of_listeners():
    from jax._src import monitoring
    preflight.place_compile_cache()          # conftest made the first call
    preflight.place_compile_cache()
    ours = {spans_lib._on_compile_span, spans_lib._on_cache_event,
            spans_lib._on_cache_duration}
    installed = (monitoring.get_event_time_span_listeners()
                 + monitoring.get_event_listeners()
                 + monitoring.get_event_duration_listeners())
    assert sorted(f.__name__ for f in installed if f in ours) == sorted(
        f.__name__ for f in ours)
    assert set(spans_lib.COMPILE_COUNTS) <= set(spans_lib.counts())


def test_default_recorder_stays_null_and_process_always_records():
    assert spans_lib.get_default() is spans_lib.NULL
    assert spans_lib.PROCESS.enabled
    assert isinstance(spans_lib.PROCESS, spans_lib.SpanRecorder)


def test_a_compile_yields_trace_lower_backend_spans_with_the_cache_verdict(
        tmp_path):
    """Miss the first time, hit after ``jax.clear_caches()``; the spans
    carry the function's name and nest under the span that paid."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    rec = spans_lib.PROCESS

    def uniquely_named_for_issue36(x):
        return jnp.tanh(x) @ x

    def mine(since):
        return [r for r in rec.records(since_seq=since)
                if "uniquely_named_for_issue36"
                in str((r.attrs or {}).get("fun"))]

    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        cc.reset_cache()
        x = jnp.ones((32, 32))
        before, mark = spans_lib.counts(), rec.last_seq()
        with rec.span("startup/compile") as paid:
            jax.jit(uniquely_named_for_issue36).lower(x).compile()
        first = mine(mark)
        assert [r.name for r in first] == ["compile/trace", "compile/lower",
                                           "compile/backend"]
        assert first[0].attrs["fun"] == "uniquely_named_for_issue36"
        assert first[2].attrs["fun"] == "jit(uniquely_named_for_issue36)"
        assert first[2].attrs["cache"] == "miss"
        assert all(r.parent == paid._seq and r.depth == 1 for r in first)
        assert first[0].t1 <= first[1].t0 + 1e-4 <= first[2].t0 + 2e-4
        jax.clear_caches()
        mark = rec.last_seq()
        jax.jit(uniquely_named_for_issue36).lower(x).compile()
        second = mine(mark)
        assert [r.name for r in second] == ["compile/trace", "compile/lower",
                                            "compile/backend"]
        assert second[2].attrs["cache"] == "hit"
        assert second[2].attrs["retrieval_s"] >= 0.0
        after = spans_lib.counts()
        assert after["compile.cache_misses"] - before[
            "compile.cache_misses"] >= 1
        assert after["compile.cache_hits"] - before["compile.cache_hits"] >= 1
        # every backend span of the window is one request, and one verdict
        backends = [r for r in rec.records()
                    if r.name == "compile/backend" and r.seq > first[0].seq]
        assert after["compile.requests"] - before["compile.requests"] == \
            len(backends)
        assert after["compile.backend_s"] - before["compile.backend_s"] == \
            pytest.approx(sum(r.seconds for r in backends), rel=1e-6)
        assert after["compile.retrieval_s"] > before["compile.retrieval_s"]
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
        cc.reset_cache()


# ---------------------------------------------------------------------------
# the program's set-up, spanned where the work happens
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    return _tiny_rcfg_and_mesh()


def test_entry_functions_are_spanned_on_the_process_recorder(tiny):
    from byol_tpu.parallel.compile_plan import build_plan
    rec = spans_lib.PROCESS
    mark = rec.last_seq()
    rcfg, mesh = _tiny_rcfg_and_mesh()
    build_plan(mesh)
    names = [r.name for r in rec.records(since_seq=mark)
             if r.name.startswith("startup/")]
    assert names == ["startup/config", "startup/mesh", "startup/resolve",
                     "startup/plan"]
    assert all(r.depth == 0 for r in rec.records(since_seq=mark)
               if r.name.startswith("startup/"))


def test_setup_training_records_the_build_and_its_seven_parts(tiny):
    rec = spans_lib.PROCESS
    mark = rec.last_seq()
    _build(*tiny)
    got = rec.records(since_seq=mark)
    (build,) = [r for r in got if r.name == "startup/build"]
    assert build.depth == 0 and build.parent == -1
    parts = sorted((r for r in got if r.parent == build.seq
                    and r.name.startswith("startup/")), key=lambda r: r.t0)
    assert [r.name for r in parts] == BUILD_PARTS
    assert [r.name for r in got if r.name.startswith("startup/build/")
            ] == BUILD_PARTS                    # and nowhere else
    assert all(r.depth == 1 for r in parts)
    assert parts[0].t0 >= build.t0 and parts[-1].t1 <= build.t1
    for a, b in zip(parts, parts[1:]):          # disjoint, in order
        assert a.t1 <= b.t0
    init = parts[1]
    assert init.attrs["leaves"] > 0
    assert init.attrs["parameters"] > 11_000_000     # a ResNet-18
    # the eager init's compiles are spans under it, on the same clock
    under_init = [r for r in got if r.parent == init.seq]
    assert under_init and {r.name for r in under_init} <= {
        "compile/trace", "compile/lower", "compile/backend"}
    assert all(init.t0 - 1e-3 <= r.t0 and r.t1 <= init.t1 + 1e-3
               for r in under_init)
    own = goodput_lib.self_seconds(got)
    assert 0.0 <= own[init.seq] <= init.seconds
    assert sum(own[r.seq] for r in parts) <= build.seconds + 1e-6


def test_under_an_outer_span_the_build_nests_and_goodput_still_partitions(
        tiny):
    rec = spans_lib.PROCESS
    meter = goodput_lib.GoodputMeter(rec)       # its window opens here
    mark = rec.last_seq()
    with rec.span("startup/outer"):
        _build(*tiny)
    with rec.span("train/dispatch"):
        time.sleep(0.005)
    got = rec.records(since_seq=mark)
    (build,) = [r for r in got if r.name == "startup/build"]
    (outer,) = [r for r in got if r.name == "startup/outer"]
    assert build.depth == 1 and build.parent == outer.seq
    p = meter.fold(scope="epoch", epoch=0)
    total = p["productive_seconds"] + sum(p["badput"].values())
    assert total == pytest.approx(p["wall_seconds"], rel=1e-9)
    assert p["badput"]["startup_compile"] == pytest.approx(outer.seconds)
    assert p["productive_seconds"] >= 0.004
    # what the ring held before the meter was made is not in its window
    assert p["wall_seconds"] < outer.seconds + 1.0


def test_the_trainer_opens_no_build_span_and_no_bare_annotate_region():
    from byol_tpu.training import trainer
    src = inspect.getsource(trainer)
    assert '"startup/build"' not in src
    assert "profiling.annotate" not in src and '"byol/' not in src
    assert "spans_lib.PROCESS" in src
