"""The reader of the program's spans, scopes and decode executions
(``program_trace``), the set-up split, the per-layer metrics that read
them, and the engine's per-kind traffic counters against the harness's
tally."""
import os
import time

import numpy as np
import pytest

import smoke_root
import harness
import program_trace
import serving
import setup_split
import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "v5e_tiny.xplane.pb")
DEV = "/device:TPU:0"


def test_recorded_chip_trace_names_each_op_by_its_framework_op():
    names = {trace_reduce.op_name(k): v
             for k, v in program_trace.framework_ops(FIXTURE).items()}
    assert names["%convolution_tanh_fusion"] == "jit(<lambda>)/dot_general"
    trace = program_trace.ProgramTrace.read(FIXTURE)
    assert len(trace.modules[DEV]) == 3      # three executions recorded
    assert trace.spans == []                 # recorded before the mirror
    assert trace.decode_executions() == []   # and with no decode kernel
    assert "jit(<lambda>)/dot_general" in program_trace.report(FIXTURE)


@pytest.mark.parametrize("name,scope", [
    ("jit(_pick_step)/while/body/attention/paged_flash_decode",
     "attention"),
    ("jit(train_step)/transpose(jvp(ssd))/mul", "ssd"),
    ("jit(train_step)/lm_head/loss/reduce_max", "loss"),
    ("jit(<lambda>)/dot_general", None),
    (None, None),
])
def test_scope_is_the_innermost_named_scope(name, scope):
    assert program_trace.scope_of(name) == scope


def _trace():
    """One device, window [0, 2000] ns (the harness annotations): ops at
    0-100, 300-400 and 700-1000 (a loop op holding a body op, inside it
    the decode kernel), nothing after; the host in a decode call's
    phases, then in ``submit``, in nothing, and in ``harvest``."""
    ops = {DEV: [("%a = f(x)", 0, 100), ("%b = g(x)", 300, 400),
                 ("%loop = while(x)", 700, 1000),
                 ("%body = h(x)", 750, 850),
                 ("%paged_flash_decode.5 = custom-call(x)", 860, 990)]}
    host = [("engine.step", 0, 1000), ("decode", 20, 990),
            ("rows.build", 100, 250), ("rows.launch", 250, 320),
            ("rows.wait", 320, 700), ("rows.commit", 990, 1000),
            ("submit", 1000, 1400), ("harvest", 1900, 2000)]
    framework = {"%a = f(x)": "jit(_pick_step)/qkv/dot_general",
                 "%b = g(x)": "jit(_pick_step)/attention/add",
                 "%loop = while(x)": "jit(_pick_step)/while",
                 "%body = h(x)": "jit(_pick_step)/while/body/mlp/mul",
                 "%paged_flash_decode.5 = custom-call(x)":
                     "jit(_pick_step)/while/body/attention/pallas_call"}
    modules = {DEV: [("jit__pick_step(1)", 0, 400),
                     ("jit__pick_step(1)", 700, 1000),
                     ("jit__pick_step(2)", 1000, 1900)]}
    return program_trace.ProgramTrace(ops, modules, host, framework)


def test_idle_time_goes_to_the_innermost_span_at_its_midpoint():
    trace = _trace()
    assert trace.window == (0, 2000)
    assert trace.gaps() == [(100, 300), (400, 700), (1000, 2000)]
    # midpoints 200 (rows.build), 550 (rows.wait), 1500 (no span, no
    # annotation: outside)
    assert trace.idle_by_span() == pytest.approx({
        "rows.build": 200e-9, "rows.wait": 300e-9, "outside": 1000e-9})
    assert trace.cause(1200) == "submit"     # an annotation, no span
    assert trace.cause(1950) == "harvest"


def test_device_time_per_scope_is_self_time_in_the_window():
    secs = _trace().scope_seconds()
    assert secs == pytest.approx({"qkv": 100e-9, "attention": 230e-9,
                                  "mlp": 100e-9, None: 70e-9})
    share = program_trace.scope_share(_trace(), "mlp", busy_s=500e-9)
    assert share == pytest.approx(20.0)
    assert program_trace.scope_share(_trace(), "ssd", busy_s=500e-9) is None


def test_decode_executions_are_the_programs_holding_the_kernel():
    trace = _trace()
    assert trace.decode_executions() == pytest.approx([300e-9])
    assert program_trace.median_decode_ms(trace) == pytest.approx(300e-6)
    trace.ops[DEV] = [e for e in trace.ops[DEV]
                      if "paged_flash_decode" not in e[0]]
    assert program_trace.median_decode_ms(trace) is None


def _jit(name, ts, dur, fun="f"):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "cat": "jit",
            "args": {"fun": fun}}


def test_jit_phases_count_nested_program_loads_once():
    events = [_jit("jit.trace", 0.0, 1.0, "outer"),
              _jit("jit.trace", 0.2, 0.3, "inner"),     # traced inside
              _jit("jit.lower", 0.4, 0.1, "const"),     # a constant's load
              _jit("jit.compile", 0.5, 0.2, "const"),   # while tracing
              _jit("jit.lower", 1.0, 0.5, "outer"),
              _jit("jit.compile", 2.0, 1.0, "outer"),
              {"name": "admission", "ph": "X", "ts": 2.5, "dur": 0.1,
               "args": {}},
              _jit("jit.compile", 9.0, 1.0, "check")]   # after the window
    phases = program_trace.jit_phases(events, t1=5.0)
    assert phases["trace"] == {"s": 1.0, "n": 1}
    assert phases["lower"] == {"s": 0.5, "n": 1}
    assert phases["compile"] == {"s": 1.0, "n": 1}
    assert phases["total_s"] == pytest.approx(2.5)
    window = [{"name": "decode", "ph": "X", "ts": 5.0, "dur": 1.0,
               "args": {}}]
    assert program_trace.setup_jit_seconds(events, window) == \
        pytest.approx(2.5)
    assert program_trace.setup_jit_seconds(events, []) is None
    assert program_trace.setup_jit_seconds(events[6:7], window) is None


def _decode_span(live, view):
    return {"name": "decode", "ph": "X", "ts": 10.0, "dur": 1.0,
            "args": {"tick": 1, "live_keys": live, "view_keys": view,
                     "view_pages": 2}}


@pytest.fixture
def global_jit_spans(monkeypatch):
    """Program-load spans in the process-global telemetry: 2.5 s of them
    before the window's first span (at 10 s)."""
    events = [_jit("jit.trace", 0.0, 1.0), _jit("jit.lower", 1.0, 0.5),
              _jit("jit.compile", 2.0, 1.0)]
    monkeypatch.setattr(program_trace, "global_events", lambda: events)
    return events


def _ctx():
    spans = [_decode_span(30, 100), _decode_span(10, 100),
             {"name": "prefill", "ph": "X", "ts": 10.0, "dur": 1.0,
              "args": {}}]
    trace = _trace()
    trace.framework["%body = h(x)"] = "jit(train_step)/transpose(jvp(ssd))/mul"
    return {"program_trace": trace, "spans": spans,
            "reduction": {"busy_s": 500e-9}}


NEW_METRICS = {"setup_jit_s.chat": 2.5,
               "setup_jit_s.docqa": 2.5,
               "decode_call_ms.chat": 300e-6,
               "decode_view_live_share.chat": 20.0,
               "decode_view_live_share.docqa": 20.0,
               "ssd_scan_share.train": 20.0}


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_layer_reader_reads_a_synthetic_run(metric, global_jit_spans):
    value = harness.layer_reader(metric).read(_ctx())
    assert value == pytest.approx(NEW_METRICS[metric])


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_layer_reader_finds_nothing_in_a_program_without_it(
        metric, monkeypatch):
    """A program with no mirrored spans, no program-load spans, no
    scopes, no view counts, and a trace whose programs hold no decode
    kernel reads None, not an error."""
    monkeypatch.setattr(program_trace, "global_events", lambda: [])
    trace = _trace()
    bare = program_trace.ProgramTrace(
        {DEV: [e for e in trace.ops[DEV] if "paged" not in e[0]]},
        trace.modules,
        [(n, s, e) for n, s, e in [("engine.step", 0, 1000)]],
        {k: "jit(_pick_step)/dot_general" for k in trace.framework})
    ctx = {"program_trace": bare, "reduction": {"busy_s": 500e-9},
           "spans": [{"name": "decode", "ph": "X", "ts": 0.0, "dur": 1.0,
                      "args": {"tick": 1}}]}
    assert harness.layer_reader(metric).read(ctx) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke_root.make(str(tmp_path_factory.mktemp("kinds")))


@pytest.mark.parametrize("shared", [False, True])
def test_traffic_stats_per_kind_agree_with_the_harness_tally(root, shared):
    """``traffic_stats`` split by kind of row counts what
    ``serving.Interval`` tallies from the tokens each request emitted."""
    cell = harness.Cell("qwen3-smoke.smoke-docqa", root)
    _, _, _, engine, _ = serving.setup(cell, 2**31 + 17, False,
                                       lambda m: None)
    rng = np.random.default_rng(7)
    doc = rng.integers(0, 256, 29).astype(np.int32)
    reqs = []
    for i in range(5):
        head = doc if shared else rng.integers(0, 256, 29).astype(np.int32)
        q = rng.integers(0, 256, 2 + i).astype(np.int32)
        reqs.append(serving.Req(np.concatenate([head, q]), 4))
    before = engine.traffic_stats()
    iv = serving.Interval()
    iv.begin(engine)
    for r in reqs[:2]:
        r.rid = engine.submit(r.prompt)
    later = reqs[2:]
    while engine.pending() or later:
        if not engine.pending():
            for r in later:
                r.rid = engine.submit(r.prompt)
            later = []
        engine.step()
        active = {q.rid: q.n_generated for q in engine.sched.active()}
        for r in reqs:
            if r.rid is None:
                continue
            n = len(engine.results[r.rid]) if r.rid in engine.results \
                else active.get(r.rid, r.n)
            if n > r.n:
                iv.emit(r, n)
                r.n = n
    iv.close(engine)
    after = engine.traffic_stats()
    delta = {k: after[k] - before[k]
             for k in ("prefill_tokens", "decode_rows", "decode_keys")}
    assert iv.decode_rows > 0 and (iv.matched > 0) == shared
    assert delta == {"prefill_tokens": iv.prompt_computed,
                     "decode_rows": iv.decode_rows,
                     "decode_keys": iv.decode_keys}


def test_setup_split_accounts_for_the_set_up(root):
    """The set-up of a smoke serving cell, split: program loads by phase,
    the weights, the warm-up, and the rest, which add up to set-up."""
    import repro.obs as obs
    cell = harness.Cell("qwen3-smoke.smoke-chat", root)
    prev = obs.get_telemetry()
    try:
        split = setup_split.measure(cell, 2**33 + 5, time.monotonic(),
                                    lambda m: None)
    finally:
        obs.set_telemetry(prev if prev is not obs._DISABLED else None)
    jit = split["jit"]
    assert jit["trace"]["n"] > 0 and jit["compile"]["n"] > 0
    assert 0 < jit["total_s"] <= split["setup_s"]
    assert split["weights_s"] > 0 and split["warm_up_s"] > 0
    assert split["rest_s"] >= 0
    assert split["programs"] and all(s > 0 for _, s in split["programs"])
    # serving.setup and harness.jax_key are put back as they were
    assert serving.warm_up.__module__ == "serving"
    assert harness.jax_key.__module__ == "harness"
