"""The observability layer: spans, the metrics registry, trace export,
and the bottleneck-attribution report.

Contracts pinned here (docs/observability.md):

* disabled telemetry is a true no-op — ``span()`` returns the shared
  singleton (identity, not equality), the registry does not grow, and no
  trace file appears;
* span nesting wires parent/trace ids through the thread-local stack and
  durations are monotonic (child <= parent, both >= the slept time);
* fixed-bucket histograms give EXACT percentiles when samples sit on
  bucket bounds (upper-bound quantile semantics);
* the JSONL trace round-trips through :func:`telemetry.read_trace`
  schema-valid;
* ``Session.explain`` reproduces ``benchmarks/fig6_fig7_breakdown.py``'s
  formulas bit-for-bit (same Metrics in, same numbers out);
* injected backend faults surface as resilience events in the trace;
* while a ``jax.profiler`` trace records, every span marks it by name,
  with telemetry on or off, and ``Session.evaluate`` nests its
  ``session.validate`` and ``session.dispatch`` spans there.
"""
from __future__ import annotations

import math
import time

import pytest

from faults import CountingHook, inject_fault
from repro import telemetry
from repro.api import Session
from repro.cnn.registry import get_cnn
from repro.core.telemetry import _NOOP, _REGISTRY, Histogram, Span
from repro.fpga.archs import make_arch
from repro.fpga.boards import get_board

NET = "mobilenetv2"
BOARD = "zc706"


@pytest.fixture()
def clean_telemetry(tmp_path):
    """Telemetry enabled with a fresh registry and a tmp trace dir;
    restores the disabled default afterwards so no other test sees it."""
    telemetry.disable()
    telemetry.reset()
    telemetry.enable(str(tmp_path))
    try:
        yield tmp_path
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.fixture()
def disabled_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


# --------------------------------------------------------------------------
# disabled mode: a true no-op
# --------------------------------------------------------------------------
def test_disabled_span_is_the_shared_singleton(disabled_telemetry):
    s1 = telemetry.span("a", {"k": 1})
    s2 = telemetry.span("b")
    assert s1 is _NOOP and s2 is _NOOP, \
        "disabled span() must return THE no-op singleton (no allocation)"
    assert telemetry.current_span() is _NOOP
    with s1 as s:
        s.set_attr("x", 1)
        s.add_event("e")


def test_disabled_mode_no_registry_growth_no_trace(disabled_telemetry,
                                                   tmp_path):
    size0 = _REGISTRY.size()
    telemetry.count("c")
    telemetry.gauge("g", 1.0)
    telemetry.observe("h", 0.5)
    telemetry.event("e", {"k": "v"})
    with telemetry.span("s", {"a": 1}):
        pass
    assert _REGISTRY.size() == size0 == 0
    assert telemetry.trace_path() is None
    assert list(tmp_path.iterdir()) == []
    snap = telemetry.snapshot()
    assert snap["enabled"] is False
    assert snap["counters"] == snap["gauges"] == snap["histograms"] == {}


# --------------------------------------------------------------------------
# the profiler's trace: spans reach it, enabled or not
# --------------------------------------------------------------------------
WINDOW = "test.window"


def _profiled(tmp_path, work):
    """Run ``work()`` under a ``jax.profiler`` trace (Python tracer off)
    inside a ``WINDOW`` annotation; return its host annotations as
    ``{name: [(start_ns, end_ns), ...]}``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "prof"), profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW):
            work()
    path, = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return spans


def test_annotation_only_span_while_profiling_disabled(disabled_telemetry,
                                                       tmp_path):
    size0 = _REGISTRY.size()
    got = []

    def work():
        with telemetry.span("probe.outer", {"k": 1}) as sp:
            got.append(sp)
            sp.set_attr("x", 1)
            sp.add_event("e")
            with telemetry.span("probe.inner"):
                pass

    spans = _profiled(tmp_path, work)
    assert got[0] is not _NOOP and not isinstance(got[0], Span)
    assert telemetry.span("after") is _NOOP, \
        "the profiler stopped: back to the shared singleton"
    assert _REGISTRY.size() == size0 == 0
    assert telemetry.trace_path() is None
    (o0, o1), = spans["probe.outer"]
    (i0, i1), = spans["probe.inner"]
    (w0, w1), = spans[WINDOW]
    assert w0 <= o0 <= i0 <= i1 <= o1 <= w1


@pytest.mark.parametrize("enabled", [False, True],
                         ids=["telemetry_off", "telemetry_on"])
def test_session_evaluate_spans_on_the_profiler_trace(enabled, tmp_path,
                                                      disabled_telemetry):
    """``session.evaluate`` encloses ``session.validate`` then
    ``session.dispatch`` on the profiler's host trace, inside the window,
    whether telemetry records or not; when it records, the JSONL trace
    holds the same spans."""
    import numpy as np

    from repro.core.dse import sample_mixed

    net, dev = get_cnn(NET), get_board(BOARD)
    ses = Session(dev)
    db = sample_mixed(np.random.default_rng(0), len(net), 32)
    ses.evaluate(db, net)                      # compile outside the trace
    if enabled:
        telemetry.enable(str(tmp_path / "jsonl"))
    spans = _profiled(tmp_path, lambda: {
        k: np.asarray(v) for k, v in ses.evaluate(db, net).items()})
    (w0, w1), = spans[WINDOW]
    (e0, e1), = spans["session.evaluate"]
    (v0, v1), = spans["session.validate"]
    (d0, d1), = spans["session.dispatch"]
    assert w0 <= e0 <= v0 <= v1 <= d0 <= d1 <= e1 <= w1
    if enabled:
        names = [l["name"] for l in
                 telemetry.read_trace(telemetry.trace_path())]
        assert names == ["session.validate", "session.dispatch",
                         "session.evaluate"]


# --------------------------------------------------------------------------
# spans: nesting + timing monotonicity
# --------------------------------------------------------------------------
def test_span_nesting_and_timing_monotonic(clean_telemetry):
    with telemetry.span("outer", {"who": "test"}) as outer:
        time.sleep(0.01)
        with telemetry.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id == outer.span_id
            assert telemetry.current_span() is inner
            time.sleep(0.01)
        assert telemetry.current_span() is outer
    assert inner.dur_s >= 0.01
    assert outer.dur_s >= inner.dur_s, \
        "a parent span can never be shorter than a child it encloses"
    # the span histogram recorded both
    snap = telemetry.snapshot()
    assert snap["histograms"]["span.outer.s"]["count"] == 1
    assert snap["histograms"]["span.inner.s"]["count"] == 1


def test_event_attaches_to_current_span_and_counts(clean_telemetry):
    with telemetry.span("work") as sp:
        telemetry.event("tick", {"n": 1})
    assert [e["name"] for e in sp.events] == ["tick"]
    assert telemetry.snapshot()["counters"]["event.tick"] == 1


# --------------------------------------------------------------------------
# histogram bucket math: exact percentiles on synthetic data
# --------------------------------------------------------------------------
def test_histogram_exact_percentiles_on_bucket_bounds():
    # 100 samples sitting exactly on the bounds 1..100: the q-quantile
    # observation IS bound ceil(100q) — upper-bound semantics make the
    # percentile exact, no interpolation error
    bounds = tuple(float(i) for i in range(1, 101))
    h = Histogram(bounds)
    for v in bounds:
        h.observe(v)
    assert h.percentile(0.50) == 50.0
    assert h.percentile(0.99) == 99.0
    assert h.percentile(0.999) == 100.0
    assert h.percentile(1.0) == 100.0
    assert h.total == 100 and h.sum == sum(bounds)
    d = h.as_dict()
    assert (d["p50"], d["p99"], d["p999"]) == (50.0, 99.0, 100.0)
    assert d["mean"] == pytest.approx(50.5)


def test_histogram_overflow_empty_and_validation():
    h = Histogram((1.0, 2.0))
    assert math.isnan(h.percentile(0.5))
    h.observe(5.0)                       # beyond the last bound
    assert h.percentile(0.5) == float("inf")
    h.observe(0.5)
    assert h.percentile(0.5) == 1.0      # first bucket's upper bound
    with pytest.raises(ValueError):
        Histogram((2.0, 1.0))
    with pytest.raises(ValueError):
        h.percentile(0.0)


def test_registry_observe_and_prometheus_export(clean_telemetry):
    for v in (0.001, 0.002, 0.004):
        telemetry.observe("lat", v, bounds=(0.001, 0.002, 0.004))
    telemetry.count("calls", 2)
    telemetry.gauge("depth", 7)
    text = telemetry.prometheus_text()
    assert "# TYPE repro_calls counter" in text
    assert "repro_calls 2" in text
    assert "repro_depth 7" in text
    assert 'repro_lat_bucket{le="0.002"} 2' in text
    assert 'repro_lat_bucket{le="+Inf"} 3' in text
    assert "repro_lat_count 3" in text


# --------------------------------------------------------------------------
# JSONL export round-trip
# --------------------------------------------------------------------------
def test_trace_jsonl_round_trip(clean_telemetry):
    with telemetry.span("outer", {"k": "v"}):
        telemetry.event("ping", {"i": 3})
        with telemetry.span("inner"):
            pass
    path = telemetry.trace_path()
    assert path is not None
    lines = telemetry.read_trace(path)          # raises on schema problems
    kinds = [(l["type"], l["name"]) for l in lines]
    # spans export on exit: inner closes before outer
    assert kinds == [("event", "ping"), ("span", "inner"),
                     ("span", "outer")]
    outer = lines[-1]
    inner = lines[-2]
    assert inner["parent"] == outer["span"]
    assert inner["trace"] == outer["trace"] == outer["span"]
    assert outer["attrs"] == {"k": "v"}
    assert [e["name"] for e in outer["events"]] == ["ping"]
    assert all(telemetry.validate_trace_line(l) == [] for l in lines)


def test_validate_trace_line_rejects_malformed():
    assert telemetry.validate_trace_line([]) != []
    assert telemetry.validate_trace_line({"type": "nope"}) != []
    missing = {"type": "span", "name": "x"}
    assert any("missing" in p for p in telemetry.validate_trace_line(missing))
    bad = {"type": "span", "name": "x", "trace": 1, "span": 1,
           "t_wall": 0.0, "dur_s": -1.0, "attrs": {}, "events": []}
    assert any("negative" in p for p in telemetry.validate_trace_line(bad))


# --------------------------------------------------------------------------
# the Session wiring: spans from every entry point + observability()
# --------------------------------------------------------------------------
def test_session_entry_points_emit_spans(clean_telemetry):
    net, dev = get_cnn(NET), get_board(BOARD)
    ses = Session(dev)
    ses.evaluate("{L1-Last:CE1-CE4}", net)
    ses.explore(net, n=32, chunk=32, seed=0)
    fut = ses.submit(["{L1-Last:CE1-CE4}"], net)
    fut.result(timeout=60)
    # the future resolves INSIDE the drain's megabatch span — give the
    # drain thread a beat to exit the span and flush its trace line
    want_names = {"session.evaluate", "session.explore", "session.submit",
                  "session.megabatch"}
    deadline = time.monotonic() + 5.0
    while True:
        names = {l["name"]
                 for l in telemetry.read_trace(telemetry.trace_path())}
        if want_names <= names or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    for want in want_names:
        assert want in names, f"no {want} span exported"
    snap = telemetry.snapshot()
    assert snap["counters"]["session.scalar_evals"] >= 1
    assert snap["histograms"]["session.request_latency_s"]["count"] == 1
    obs = ses.observability()
    assert set(obs) == {"compile", "stats", "caches", "breaker",
                        "telemetry"}
    assert obs["stats"]["submits"] == 1
    assert obs["telemetry"]["enabled"] is True


def test_fault_injection_emits_resilience_events(clean_telemetry):
    net, dev = get_cnn(NET), get_board(BOARD)
    # design_tile=11 is unique to this test so the primary really traces
    # (and faults) instead of reusing a cached compile
    ses = Session(dev, backend="pallas_interpret", design_tile=11,
                  fallback_backend="ref", max_retries=0)
    specs = [make_arch("segmented", net, 4)]
    with inject_fault(CountingHook(backend="pallas_interpret")):
        for _ in range(ses.breaker.fail_threshold):
            ses.evaluate(specs, net)
    assert ses.breaker.is_open
    events = [l for l in telemetry.read_trace(telemetry.trace_path())
              if l["type"] == "event"]
    names = {e["name"] for e in events}
    assert "resilience.degrade" in names
    assert "resilience.breaker_open" in names
    assert telemetry.snapshot()["counters"]["session.degraded"] \
        == ses.stats.degraded


# --------------------------------------------------------------------------
# Session.explain: bit-for-bit parity with the fig6/fig7 formulas
# --------------------------------------------------------------------------
def test_explain_matches_fig6_fig7_formulas():
    """The report must BE the benchmark's analysis: every number derives
    from the same ``Metrics`` by the same formula, compared exactly."""
    net, dev = get_cnn("resnet50"), get_board(BOARD)
    ses = Session(dev)
    spec = make_arch("segmented_rr", net, 6)
    m = ses.evaluate(spec, net)
    rep = ses.explain(spec, net)

    # Fig. 6 layer granularity (fig6_fig7_breakdown.py lines)
    want_mem_bound = [r.layer.index for b in m.blocks for r in b.per_layer
                      if r.mem_cycles > r.compute_cycles]
    want_idle = (sum(max(r.mem_cycles - r.compute_cycles, 0.0)
                     for b in m.blocks for r in b.per_layer)
                 / sum(max(r.mem_cycles, r.compute_cycles)
                       for b in m.blocks for r in b.per_layer))
    assert rep["mem_bound_layers"] == want_mem_bound
    assert rep["idle_fraction"] == want_idle          # bit-for-bit
    assert len(want_mem_bound) > 0, \
        "SegmentedRR on ResNet50/ZC706 must show memory-bound layers"

    # Fig. 7 access split — exact Metrics fields, no re-derivation drift
    assert rep["access"]["weights_bytes"] == float(m.weight_access_bytes)
    assert rep["access"]["fm_bytes"] == float(m.fm_access_bytes)
    assert rep["access"]["total_bytes"] == float(m.access_bytes)
    assert rep["access"]["dominant"] == (
        "weights" if m.weight_access_bytes > m.fm_access_bytes else "fms")

    # segment ranking: occupancy-descending, shares sum to 1
    occs = [d["occupancy_s"] for d in rep["segments"]]
    assert occs == sorted(occs, reverse=True)
    assert sum(d["share"] for d in rep["segments"]) == pytest.approx(1.0)
    total = sum(max(s.compute_s, s.mem_s) for s in m.per_segment)
    for d in rep["segments"]:
        s = m.per_segment[d["index"]]
        assert d["occupancy_s"] == max(s.compute_s, s.mem_s)
        assert d["share"] == max(s.compute_s, s.mem_s) / total
        assert d["bound"] == ("memory" if s.mem_s > s.compute_s
                              else "compute")

    # CE ranking mirrors Metrics.ce_busy_s; the top CE bounds throughput
    assert {c["ce"]: c["busy_s"] for c in rep["ces"]} == m.ce_busy_s
    busiest = max(m.ce_busy_s.values())
    assert rep["bottleneck"]["ce_busy_s"] == busiest

    # summary is the Metrics headline, verbatim
    assert rep["summary"]["latency_s"] == m.latency_s
    assert rep["summary"]["throughput_ips"] == m.throughput_ips

    # the renderer covers every section without crashing
    text = telemetry.format_report(rep)
    assert "bottleneck: segment" in text and "idle fraction" in text


def test_explain_rejects_batches():
    from repro.api import EvalError

    net, dev = get_cnn(NET), get_board(BOARD)
    ses = Session(dev)
    with pytest.raises(EvalError):
        ses.explain(["{L1-Last:CE1-CE4}"], net)


# --------------------------------------------------------------------------
# search telemetry: per-generation counters/gauges
# --------------------------------------------------------------------------
def test_dse_search_emits_generation_telemetry(clean_telemetry):
    from repro.core.dse.search import SearchConfig

    net, dev = get_cnn(NET), get_board(BOARD)
    ses = Session(dev)
    ses.explore(net, n=128, strategy="search", seed=0,
                config=SearchConfig(pop_size=64, seed=0))
    snap = telemetry.snapshot()
    gens = [l for l in telemetry.read_trace(telemetry.trace_path())
            if l["name"] == "dse.generation"]
    assert len(gens) >= 1
    assert snap["counters"]["dse.generations"] == len(gens)
    assert "dse.front_size" in snap["gauges"]
    assert gens[-1]["attrs"]["front"] == snap["gauges"]["dse.front_size"]
