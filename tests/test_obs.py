"""Unified telemetry subsystem: metrics registry, span tracer, Chrome
trace export, serve/train wiring, and the live roofline accountant.

Covers the observability PR's acceptance checklist: span nesting +
thread-safety, Chrome trace-event schema validity (perfetto-required
fields), metrics snapshot determinism under chaos virtual-clock replay,
serve spans covering admission -> prefill -> decode -> completion,
``engine.telemetry()`` contents, and observed-vs-predicted roofline rows
for one conv2d and one paged-decode workload within the documented
tolerances."""
import importlib.util
import json
import os
import re
import sys
import threading

import numpy as np
import pytest

import repro.obs as obs
from repro.obs import MetricsRegistry, SpanTracer, Telemetry
from repro.obs.roofline_live import (TOLERANCES, TrafficRow,
                                     paged_decode_rows,
                                     predict_paged_decode_traffic)


@pytest.fixture(autouse=True)
def _isolate_global_telemetry():
    """Every test leaves the process-global telemetry disabled and the
    global registry as it found it (other test files must not inherit an
    enabled tracer)."""
    prev = obs.get_telemetry()
    yield
    obs.set_telemetry(prev if prev is not obs._DISABLED else None)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_hists():
    m = MetricsRegistry()
    m.counter("reqs", outcome="ok")
    m.counter("reqs", 2, outcome="ok")
    m.counter("reqs", outcome="shed")
    m.gauge("util", 0.25)
    m.gauge("util", 0.83)                      # last write wins
    for v in (1.0, 3.0, 2.0):
        m.observe("lat_s", v)
    snap = m.snapshot()
    assert snap["counters"] == {"reqs{outcome=ok}": 3,
                                "reqs{outcome=shed}": 1}
    assert snap["gauges"] == {"util": 0.83}
    h = snap["histograms"]["lat_s"]
    assert (h["count"], h["sum"], h["min"], h["max"]) == (3, 6.0, 1.0, 3.0)
    assert h["mean"] == 2.0 and h["p50"] == 2.0
    assert m.get_counter("reqs", outcome="ok") == 3
    assert m.get_counter("missing") == 0


def test_registry_label_order_is_canonical():
    m = MetricsRegistry()
    m.counter("x", a=1, b=2)
    m.counter("x", b=2, a=1)                   # same series, any kw order
    assert m.snapshot()["counters"] == {"x{a=1,b=2}": 2}


def test_registry_absorb_flattens_nested_stats():
    m = MetricsRegistry()
    m.absorb({"hits": 3, "hit": True, "name": "skipme",
              "nested": {"depth": 2.5}}, prefix="kv.", mode="paged")
    g = m.snapshot()["gauges"]
    assert g["kv.hits{mode=paged}"] == 3.0
    assert g["kv.hit{mode=paged}"] == 1.0
    assert g["kv.nested.depth{mode=paged}"] == 2.5
    assert not any("name" in k for k in g)     # non-numeric skipped


def test_registry_reset_by_name():
    m = MetricsRegistry()
    m.counter("keep")
    m.counter("drop", lbl="x")
    m.reset(["drop"])
    assert m.snapshot()["counters"] == {"keep": 1}
    m.reset()
    assert m.snapshot()["counters"] == {}


def test_registry_thread_safety():
    m = MetricsRegistry()
    N, PER = 8, 500

    def work(tid):
        for i in range(PER):
            m.counter("ops", worker=tid % 2)
            m.observe("v", float(i))

    ts = [threading.Thread(target=work, args=(t,)) for t in range(N)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = m.snapshot()
    assert sum(snap["counters"].values()) == N * PER
    assert snap["histograms"]["v"]["count"] == N * PER


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def _vclock_tracer():
    clk = [0.0]

    def tick(dt=1.0):
        clk[0] += dt

    return SpanTracer(clock=lambda: clk[0], process_name="test"), tick


def test_span_nesting_and_ordering():
    tr, tick = _vclock_tracer()
    with tr.span("outer", phase="a"):
        tick()
        with tr.span("inner"):
            tick()
        tick()
    evs = tr.spans()
    # completion order: inner closes before outer
    assert [e["name"] for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"] == {"phase": "a"}


def test_begin_finish_force_closes_dangling_children():
    tr, tick = _vclock_tracer()
    run = tr.begin("RUN", step=0)
    tick()
    tr.begin("dangling")                       # never finished explicitly
    tick()
    tr.finish(run, end_step=5)
    names = [e["name"] for e in tr.spans()]
    assert names == ["dangling", "RUN"]
    assert tr.spans("RUN")[0]["args"] == {"step": 0, "end_step": 5}
    tr.finish(run)                             # idempotent
    assert len(tr.spans("RUN")) == 1


def test_tracer_decorator_and_instants():
    tr, tick = _vclock_tracer()

    @tr.trace("step")
    def step():
        tick()
        tr.instant("fault", cat="chaos", host=1)
        return 7

    assert step() == 7
    assert len(tr.spans("step")) == 1
    (inst,) = [e for e in tr.events() if e["ph"] == "i"]
    assert inst["name"] == "fault" and inst["args"] == {"host": 1}


def test_tracer_threads_interleave_without_corruption():
    tr, _ = _vclock_tracer()
    N, PER = 4, 50

    def work():
        for i in range(PER):
            with tr.span("w"):
                with tr.span("wi"):
                    pass

    ts = [threading.Thread(target=work) for _ in range(N)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(tr.spans("w")) == N * PER
    assert len(tr.spans("wi")) == N * PER
    assert tr.dropped == 0


def test_tracer_bounded_buffer_drops_oldest():
    tr = SpanTracer(clock=lambda: 0.0, max_events=10)
    for i in range(25):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 10
    assert tr.dropped == 15
    assert tr.events()[0]["name"] == "e15"     # oldest dropped first


def test_chrome_trace_schema(tmp_path):
    tr, tick = _vclock_tracer()
    with tr.span("outer"):
        tick(0.5)
        tr.instant("mark")
    path = tr.write_chrome_trace(str(tmp_path / "sub" / "trace.json"))
    with open(path) as f:
        doc = json.load(f)                     # valid JSON round-trip
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    for e in evs:                              # perfetto-required fields
        for field in ("name", "ph", "ts", "pid", "tid"):
            assert field in e, (field, e)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    phs = {e["ph"] for e in evs}
    assert {"M", "X", "i"} <= phs
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(m["name"] == "process_name" and
               m["args"]["name"] == "test" for m in meta)
    assert any(m["name"] == "thread_name" for m in meta)
    (x,) = [e for e in evs if e["ph"] == "X"]
    assert x["dur"] == pytest.approx(0.5e6)    # seconds -> microseconds
    (i,) = [e for e in evs if e["ph"] == "i"]
    assert i["s"] == "t"


# ---------------------------------------------------------------------------
# Telemetry facade
# ---------------------------------------------------------------------------

def test_disabled_telemetry_is_inert():
    t = Telemetry(enabled=False, registry=MetricsRegistry())
    with t.span("s") as h:
        assert h is None
    assert t.begin("b") is None
    t.finish(None)                             # no-op, no raise
    t.instant("i")
    t.counter("c")
    assert t.tracer.events() == []
    assert t.snapshot()["counters"] == {}


def test_enable_installs_and_restores_global():
    assert obs.get_telemetry().enabled is False
    t = obs.enable(process_name="unit")
    assert obs.get_telemetry() is t and t.enabled
    obs.set_telemetry(None)
    assert obs.get_telemetry().enabled is False


def test_write_metrics_artifact(tmp_path):
    t = Telemetry(registry=MetricsRegistry())
    t.counter("c", kind="x")
    p = t.write_metrics(str(tmp_path / "m.json"), extra={"serve": {"n": 1}})
    with open(p) as f:
        doc = json.load(f)
    assert doc["counters"] == {"c{kind=x}": 1}
    assert doc["serve"] == {"n": 1}


# ---------------------------------------------------------------------------
# serving engine wiring: spans + telemetry() + observed traffic
# ---------------------------------------------------------------------------

def _serve_traced(*, n_requests=3, prompt_len=11, max_new=5, page_size=8,
                  prefill_chunk=8, prefix_cache=False, prefix_share=0.0,
                  seed=0):
    from repro.launch.serve import build_engine
    tel = Telemetry(enabled=True, registry=MetricsRegistry())
    engine, vocab = build_engine(
        "qwen3-4b", slots=3, max_len=64, max_new=max_new, kv_mode="paged",
        page_size=page_size, prefill_chunk=prefill_chunk,
        prefix_cache=prefix_cache, seed=seed, telemetry=tel)
    rng = np.random.default_rng(seed)
    prompts = []
    common = rng.integers(0, vocab, size=prompt_len // 2)
    for i in range(n_requests):
        p = rng.integers(0, vocab, size=prompt_len).astype(np.int32)
        if prefix_share > 0 and i % max(1, round(1 / prefix_share)) == 0:
            p[:len(common)] = common
        prompts.append(p)
        engine.submit(p)
    results = engine.run()
    return engine, tel, prompts, results


def test_serve_spans_cover_request_lifecycle():
    engine, tel, prompts, results = _serve_traced()
    assert len(results) == 3 and all(len(v) == 5 for v in results.values())
    names = {e["name"] for e in tel.tracer.events()}
    assert {"admission", "prefill", "decode", "admit", "complete"} <= names
    # every request admitted and completed exactly once
    admits = [e for e in tel.tracer.events() if e["name"] == "admit"]
    completes = [e for e in tel.tracer.events()
                 if e["name"] == "complete"]
    assert sorted(e["args"]["rid"] for e in admits) == [0, 1, 2]
    assert sorted(e["args"]["rid"] for e in completes) == [0, 1, 2]
    # lifecycle ordering per request: admit before its completion
    t_admit = {e["args"]["rid"]: e["ts"] for e in admits}
    t_done = {e["args"]["rid"]: e["ts"] for e in completes}
    assert all(t_admit[r] <= t_done[r] for r in t_admit)
    # prefill spans precede the first pure-decode span
    prefills = tel.tracer.spans("prefill")
    decodes = tel.tracer.spans("decode")
    assert prefills and decodes
    assert min(s["ts"] for s in prefills) <= min(s["ts"] for s in decodes)


def test_engine_telemetry_snapshot_contents():
    engine, tel, _, results = _serve_traced(n_requests=5,
                                            prefix_cache=True,
                                            prefix_share=0.5)
    snap = engine.telemetry()
    assert snap["mode"] == "paged"
    assert snap["ticks"] > 0
    assert snap["outcomes"]["ok"] == len(results)
    kv = snap["kv"]
    assert {"bytes_resident", "pages_total", "pages_used",
            "utilization"} <= set(kv)
    assert 0.0 <= kv["utilization"] <= 1.0
    pf = snap["prefix"]
    assert pf["lookups"] >= 5 and pf["hits"] >= 1     # shared prefix hit
    tr = snap["traffic"]
    assert tr["gb_read_bytes"] > 0 and tr["written_bytes"] > 0
    assert tr["dram_read_bytes"] >= tr["gb_read_bytes"]  # page rounding
    # the pull half landed in the registry as serve.* gauges
    g = tel.snapshot()["gauges"]
    assert g["serve.outcomes.ok"] == float(len(results))
    assert "serve.kv.utilization" in g
    assert "serve.traffic.gb_read_bytes" in g


def test_serve_counters_count_outcomes():
    engine, tel, _, results = _serve_traced()
    m = tel.metrics
    assert m.get_counter("serve_requests", outcome="ok") == len(results)


# ---------------------------------------------------------------------------
# live roofline: observed vs predicted
# ---------------------------------------------------------------------------

def test_paged_decode_traffic_matches_prediction():
    prompt_lens, max_new, page, chunk = [11, 11, 11], 5, 8, 8
    engine, tel, prompts, _ = _serve_traced(
        n_requests=3, prompt_len=11, max_new=max_new, page_size=page,
        prefill_chunk=chunk, prefix_cache=False)
    observed = engine.telemetry()["traffic"]
    predicted = predict_paged_decode_traffic(
        prompt_lens, max_new, page_size=page,
        page_bytes=engine.kv.cfg.page_bytes, prefill_chunk=chunk)
    rows = paged_decode_rows(observed, predicted)
    levels = [r.level for r in rows]
    assert "gb" in levels and "dram" in levels
    for r in rows:
        assert r.within, r.row()
    # gb is token-exact on both sides: the two independent derivations
    # must agree exactly, not merely within tolerance
    gb = [r for r in rows if r.level == "gb" and r.unit == "bytes"][0]
    assert gb.ratio == pytest.approx(1.0)
    dram = [r for r in rows if r.level == "dram"][0]
    assert dram.observed >= gb.observed        # page rounding only adds


def test_paged_decode_prediction_accounts_prefix_hits():
    page, chunk, max_new = 8, 8, 5
    cold = predict_paged_decode_traffic(
        [16], max_new, page_size=page, page_bytes=page * 4,
        prefill_chunk=chunk)
    warm = predict_paged_decode_traffic(
        [16], max_new, page_size=page, page_bytes=page * 4,
        prefill_chunk=chunk, matched=[8])
    assert warm["gb_read_bytes"] < cold["gb_read_bytes"]
    assert warm["written_tokens"] == cold["written_tokens"] - 8


def test_conv2d_observed_vs_predicted_rows():
    from repro.obs.roofline_live import conv2d_rows
    rows = conv2d_rows(1, 16, 16, 8, 16, 3, 3)
    by_level = {r.level: r for r in rows}
    assert {"hlo_flops", "hlo_bytes", "gb"} <= set(by_level)
    for r in rows:
        assert r.predicted > 0
        assert r.within, r.row()
    # XLA must count the same MACs the analytic model does
    assert by_level["hlo_flops"].ratio == pytest.approx(1.0, rel=0.25)
    # the scheduler's fetch plan never exceeds the refetch-everything bound
    assert by_level["gb"].observed <= by_level["gb"].predicted * (1 + 1e-9)


def test_traffic_report_mirrors_gauges():
    from repro.obs.roofline_live import report
    m = MetricsRegistry()
    rows = [TrafficRow("w", "gb", 100.0, 100.0)]
    out = report(rows, registry=m)
    assert out[0]["within"] is True and out[0]["ratio"] == 1.0
    g = m.snapshot()["gauges"]
    assert g["traffic_observed{level=gb,unit=bytes,workload=w}"] == 100.0
    assert g["traffic_ratio{level=gb,unit=bytes,workload=w}"] == 1.0


def test_tolerances_documented_for_asserted_levels():
    assert TOLERANCES["gb"] <= 1.05            # near-exact invariant
    assert TOLERANCES["dram"] < 2.0            # bounded paging overhead


# ---------------------------------------------------------------------------
# train-loop wiring: chaos virtual-clock replay determinism
# ---------------------------------------------------------------------------

def _train_chaos(tmp_path, tag):
    from repro.launch.train import run
    obs.REGISTRY.reset()
    trace = tmp_path / f"trace_{tag}.json"
    out = run("qwen3-4b", steps=8, seq_len=16, global_batch=4,
              ckpt_dir=str(tmp_path / f"ckpt_{tag}"), ckpt_every=4,
              chaos=["nan@3"], trace_out=str(trace),
              metrics_out=str(tmp_path / f"m_{tag}.json"))
    obs.set_telemetry(None)
    with open(trace) as f:
        doc = json.load(f)
    return out, doc


def test_chaos_replay_metrics_and_trace_deterministic(tmp_path):
    """Two identical chaos runs on the virtual clock produce the same
    counter section and the same trace timeline (timestamps included —
    spans are clocked on the per-step virtual clock, not wall time)."""
    out1, doc1 = _train_chaos(tmp_path, "a")
    out2, doc2 = _train_chaos(tmp_path, "b")
    assert out1["telemetry"]["counters"] == out2["telemetry"]["counters"]
    assert out1["telemetry"]["counters"], "expected recorded events"

    def timeline(doc):
        return [(e["name"], e["ph"], e["ts"], e.get("dur"),
                 json.dumps(e["args"], sort_keys=True))
                for e in doc["traceEvents"] if e["ph"] in ("X", "i")]

    assert timeline(doc1) == timeline(doc2)
    names = {e["name"] for e in doc1["traceEvents"]}
    assert "RUN" in names and "chaos" in names and "guard_skip" in names


def test_gradguard_events_reach_registry(tmp_path):
    out, doc = _train_chaos(tmp_path, "g")
    c = out["telemetry"]["counters"]
    assert c.get("gradguard_events{kind=skip,trigger=nonfinite}", 0) >= 1
    assert c.get("checkpoint_ops{op=save}", 0) >= 1


# ---------------------------------------------------------------------------
# one span call, two sinks: the profiler mirror
# ---------------------------------------------------------------------------

@pytest.fixture
def annotations(monkeypatch):
    """Stands in for ``jax.profiler.TraceAnnotation``; returns the log of
    (open | close, name) it records."""
    import jax.profiler
    log = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("close", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    return log


def test_spans_mirror_into_profiler_annotations_in_nesting_order(
        annotations):
    tr, tick = _vclock_tracer()
    outer = tr.begin("outer")
    with tr.span("a"):
        tr.instant("mark")                     # instants are not mirrored
        tick()
    tr.begin("b")
    tr.begin("c")                              # both left open
    tr.finish(outer)                           # force-closes c, then b
    tr.complete("timed", 0.0, 1.0)             # timed elsewhere: no mirror
    assert annotations == [("open", "outer"), ("open", "a"),
                           ("close", "a"), ("open", "b"), ("open", "c"),
                           ("close", "c"), ("close", "b"),
                           ("close", "outer")]
    assert [e["name"] for e in tr.spans()] == ["a", "c", "b", "outer",
                                               "timed"]


def test_disabled_telemetry_builds_no_annotation(annotations):
    off = Telemetry(enabled=False, registry=MetricsRegistry())
    with off.span("s"):
        pass
    off.finish(off.begin("b"))
    assert annotations == []
    on = Telemetry(enabled=True, registry=MetricsRegistry())
    with on.span("s"):
        pass
    assert annotations == [("open", "s"), ("close", "s")]


def test_tracer_mirrors_nothing_without_jax(annotations, monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    tr, _ = _vclock_tracer()
    with tr.span("s"):
        pass
    assert annotations == [] and len(tr.spans("s")) == 1


# ---------------------------------------------------------------------------
# program loads: JAX's compile events as spans and counters
# ---------------------------------------------------------------------------

def _listeners():
    from jax._src import monitoring
    return (len(monitoring.get_event_time_span_listeners()),
            len(monitoring.get_event_listeners()))


@pytest.fixture
def compile_cache(tmp_path):
    """JAX's persistent compile cache in a directory of its own, for one
    test, every program written to it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_program_loads_become_spans_and_cache_counters(compile_cache):
    import time

    import jax
    import jax.numpy as jnp
    tel = obs.enable(process_name="unit")
    after_enable = _listeners()
    obs.enable(process_name="unit")            # once per process
    tel = obs.get_telemetry()
    assert _listeners() == after_enable

    def loaded_program(x):
        return jnp.tanh(x) * 3.0

    x = np.arange(5, dtype=np.float32)
    t0 = time.monotonic()
    jax.jit(loaded_program)(x).block_until_ready()
    jax.clear_caches()                         # the next call loads it
    jax.jit(loaded_program)(x).block_until_ready()
    t1 = time.monotonic()
    mine = [e for e in tel.tracer.spans()
            if "loaded_program" in e["args"].get("fun", "")]
    by_phase = {n: [e for e in mine if e["name"] == n]
                for n in ("jit.trace", "jit.lower", "jit.compile")}
    assert all(len(v) == 2 for v in by_phase.values()), by_phase
    for e in mine:                              # on the tracer's clock
        assert e["cat"] == "jit"
        assert t0 - 0.05 <= e["ts"] <= e["ts"] + e["dur"] <= t1 + 0.05
    c = tel.snapshot()["counters"]
    assert c.get("jit.cache_misses", 0) >= 1
    assert c.get("jit.cache_hits", 0) >= 1


def test_disabled_or_virtual_clock_telemetry_records_no_program_load():
    import jax
    import jax.numpy as jnp
    before = _listeners()
    obs.set_telemetry(Telemetry(enabled=False, registry=MetricsRegistry()))
    if obs._JitListener.installed is None:     # first in this process
        assert _listeners() == before          # disabled: none registered
    obs.set_telemetry(None)
    clk = [0.0]
    tel = obs.enable(clock=lambda: clk[0], process_name="vclock")
    jax.jit(lambda x: jnp.cos(x) + 7.0)(np.ones(3, np.float32))
    assert tel.tracer.spans() == []            # replays stay bit-identical
    obs.set_telemetry(None)
    off = obs.get_telemetry()
    jax.jit(lambda x: jnp.cos(x) + 8.0)(np.ones(3, np.float32))
    assert off.tracer.events() == []


# ---------------------------------------------------------------------------
# serving: the tick's host phases and the decode call's live page view
# ---------------------------------------------------------------------------

def _harness_annotations():
    path = os.path.join(os.path.dirname(__file__), "..", "bench",
                        "trace_reduce.py")
    spec = importlib.util.spec_from_file_location("_trace_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return set(mod.ANNOTATIONS)


def _shared_prefix_serve():
    """Two requests on one 12-token document, the second after the first
    finished: a prefix-cache hit that ends mid-page, so a copy-on-write."""
    from repro.launch.serve import build_engine
    tel = Telemetry(enabled=True, registry=MetricsRegistry())
    engine, vocab = build_engine(
        "qwen3-4b", slots=3, max_len=64, max_new=5, kv_mode="paged",
        page_size=8, prefill_chunk=8, prefix_cache=True, seed=0,
        telemetry=tel)
    rng = np.random.default_rng(0)
    doc = rng.integers(0, vocab, 12).astype(np.int32)
    for n in (3, 4):
        engine.submit(np.concatenate(
            [doc, rng.integers(0, vocab, n).astype(np.int32)]))
        engine.run()
    return engine, tel


def test_paged_tick_spans_split_host_and_device_work():
    engine, tel = _shared_prefix_serve()
    assert engine.cow_copies == 1
    spans = tel.tracer.spans()
    names = {e["name"] for e in spans}
    assert {"admission", "reclaim", "prefix_match", "cow", "prefill",
            "decode", "rows.build", "rows.launch", "rows.wait",
            "rows.commit"} <= names
    assert not names & _harness_annotations()
    cow = tel.tracer.spans("cow")[0]
    admission = [a for a in tel.tracer.spans("admission")
                 if a["ts"] <= cow["ts"]
                 and cow["ts"] + cow["dur"] <= a["ts"] + a["dur"]]
    assert len(admission) == 1                 # inside admission
    for group in tel.tracer.spans("prefill") + tel.tracer.spans("decode"):
        inside = [e["name"] for e in spans if e["name"].startswith("rows.")
                  and group["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= group["ts"] + group["dur"]]
        assert inside == ["rows.build", "rows.launch", "rows.wait",
                          "rows.commit"]


def test_decode_span_counts_the_live_page_view():
    """One 9-token prompt, chunks of 8, 5 tokens: the 1-token second chunk
    runs in the T == 1 call (a ``decode`` span with one prefill row),
    then four decode rows; every row attends the tokens before it and
    itself, under a view of 2 pages of 8 for each of the 3 slots."""
    engine, tel, prompts, _ = _serve_traced(n_requests=1, prompt_len=9,
                                            max_new=5, page_size=8,
                                            prefill_chunk=8)
    assert [e["args"]["prefill_rows"] for e in tel.tracer.spans("prefill")
            ] == [1]
    decode = [e["args"] for e in tel.tracer.spans("decode")]
    assert [(a["prefill_rows"], a["decode_rows"]) for a in decode] == \
        [(1, 0)] + [(0, 1)] * 4
    assert [a["live_keys"] for a in decode] == [9, 10, 11, 12, 13]
    assert all(a["view_pages"] == 2 and a["view_keys"] == 3 * 2 * 8
               for a in decode)
    tr = engine.traffic_stats()
    assert tr["prefill_tokens"] == 9
    assert tr["decode_rows"] == 4 and tr["decode_keys"] == 10 + 11 + 12 + 13


# ---------------------------------------------------------------------------
# named scopes in the train step
# ---------------------------------------------------------------------------

def _scopes_in(lowered) -> set[str]:
    """The scopes over the ops of a lowered program: each component but
    the last (the primitive) of the op names its locations carry, read
    through transformations (``transpose(jvp(ssd))/mul``)."""
    text = lowered.as_text(debug_info=True)
    words = set()
    for loc in re.findall(r'loc\("([^"]*/[^"]*)"', text):
        for part in loc.split("/")[:-1]:
            words.update(re.findall(r"[A-Za-z_][\w.\-]*", part))
    return words


def test_serving_step_program_carries_its_scopes():
    """The one jitted serving step (decode width and prefill width) names
    each layer's parts in its op names."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_bundle
    from repro.serving import engine as serving_engine
    b = get_bundle("qwen3-4b", smoke=True)
    params = b.abstract_params()
    pool = jax.eval_shape(lambda: b.init_paged_pool(9, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    for T in (1, 8):
        low = serving_engine._pick_step.lower(
            b.paged_step, params, i32(2, T), pool, i32(2, 4), i32(2), i32(2))
        assert "jit__pick_step" in low.as_text()
        assert {"qkv", "attention", "mlp", "lm_head"} <= _scopes_in(low)


def test_mamba2_train_step_carries_its_scopes():
    import jax
    import jax.numpy as jnp
    from repro import training
    from repro.configs import get_bundle
    from repro.optim import adamw_init
    b = get_bundle("mamba2-370m", smoke=True)
    params = b.abstract_params()
    opt = jax.eval_shape(adamw_init, params)
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    step = jax.jit(training.make_train_step(b.forward, training.TrainHyper()))
    scopes = _scopes_in(step.lower(params, opt, {"tokens": tok,
                                                 "labels": tok}))
    assert {"in_proj", "conv", "ssd", "gate_norm", "out_proj", "lm_head",
            "loss", "adamw"} <= scopes
