"""The serving driver: one general generator and load loop for every
``kind: serve`` traffic mix, driving ``ServingEngine.submit/step/cancel``.

Traffic (a ``bench/traffic/<mix>.json`` file):

* ``loop: open`` — requests arrive on the wall clock at ``rate_per_s``
  whatever the server does; each is timed from when it was due.  The
  window's requests always have the same sizes and the same gaps between
  arrivals (quantiles of the stated distributions); the seed only orders
  them and draws their tokens.  Arrivals go on past the window's close, so
  the window's last requests are served under the same load; the run
  waits for each of them to have its first token, ``drain_max_s`` at the
  most.
* ``loop: closed`` — ``clients`` clients each take the next document
  session and send its asks one after another, each when the previous
  answer is complete.  An ask is the document plus a fresh question.

The engine has no per-request output limit below ``max_new_tokens``, so a
request is cancelled once it has its drawn number of tokens; that counts
as completed.

After the window the engine is dropped and a sample of finished requests,
drawn from the seed and holding the longest, is checked against the
configuration's float32 reference.
"""
from __future__ import annotations

import gc
import math
import statistics
import time

import numpy as np

import harness

# ---------------------------------------------------------------------------
# sizes from distributions, as fixed sets
# ---------------------------------------------------------------------------


def _norm_ppf(p):
    return statistics.NormalDist().inv_cdf(p)


def quantile_sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` sizes at the evenly spaced quantiles (i + 0.5) / n of
    ``dist``, clipped to its range: the same multiset for every seed."""
    p = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + p * (dist["max"] + 1 - dist["min"])
    elif kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] *
                                    np.array([_norm_ppf(q) for q in p]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo = dist.get("min", -np.inf)
    hi = dist.get("max", np.inf)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


class Req:
    __slots__ = ("prompt", "out_len", "due", "measured", "client", "rid",
                 "submitted", "first_t", "last_t", "done_t", "n", "served",
                 "outcome")

    def __init__(self, prompt, out_len, due=0.0, measured=True, client=-1):
        self.prompt = prompt
        self.out_len = int(out_len)
        self.due = float(due)
        self.measured = measured
        self.client = client
        self.rid = None
        self.submitted = None
        self.first_t = None
        self.last_t = None
        self.done_t = None
        self.n = 0
        self.served = None
        self.outcome = None


def open_loop_plan(traffic: dict, seconds: float, vocab: int, seed: int):
    """Requests of the window (measured) and of the drain after it."""
    rng = np.random.default_rng(seed)
    rate = traffic["rate_per_s"]
    n_win = max(1, round(rate * seconds))
    n_tail = math.ceil(rate * traffic["drain_max_s"])
    plan = []
    start = 0.0
    for n, measured in ((n_win, True), (n_tail, False)):
        plens = rng.permutation(quantile_sizes(traffic["prompt"], n))
        olens = rng.permutation(quantile_sizes(traffic["output"], n))
        gaps = rng.permutation(exponential_gaps(rate, n))
        if measured:
            gaps *= seconds / gaps.sum()       # the window's arrivals span it
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for p, o, d in zip(plens, olens, due):
            plan.append(Req(rng.integers(0, vocab, p).astype(np.int32), o, d,
                            measured))
        start = start + gaps.sum()
    return plan


def sessions(traffic: dict, vocab: int, seed: int):
    """Document sessions for a closed loop, without end: each round of
    ``sessions`` sessions has the same document sizes, ask counts and
    question sizes, in an order and with tokens drawn from the seed."""
    rng = np.random.default_rng(seed)
    n = traffic["sessions"]
    doc_sizes = quantile_sizes(traffic["document"], n)
    ask_counts = quantile_sizes({"dist": "uniform", **traffic["asks"]}, n)
    q_sizes = quantile_sizes(traffic["question"], int(ask_counts.sum()))
    while True:
        docs, asks = rng.permutation(doc_sizes), rng.permutation(ask_counts)
        qs = iter(rng.permutation(q_sizes))
        for d, a in zip(docs, asks):
            doc = rng.integers(0, vocab, d).astype(np.int32)
            yield [np.concatenate([doc, rng.integers(0, vocab, next(qs))
                                   .astype(np.int32)]) for _ in range(a)]


# ---------------------------------------------------------------------------
# warm-up: every (chunk width, page view) program the traffic can reach
# ---------------------------------------------------------------------------

def _pow2(n):
    b = 1
    while b < n:
        b *= 2
    return b


def warm_shapes(traffic: dict) -> list[tuple[int, int]]:
    """Every (T, page view) shape of ``paged_step`` that the mix's
    lengths can reach.

    With a prefill budget of at least slots x chunk no chunk is cut
    short, so a prompt of length L runs chunks of ``chunk`` tokens and a
    last one of L mod chunk.  A first chunk's page view follows from its
    own width; a later chunk, a suffix after a prefix hit and a decode row
    can have any width up to the chunk under any view past the first
    chunk's, and rows of one call take the widest width and the largest
    view among them."""
    eng = traffic["engine"]
    C, page, max_len = eng["prefill_chunk"], eng["page_size"], eng["max_len"]
    lo, hi = prompt_range(traffic)
    per_slot = -(-max_len // page)

    def view(tokens):
        return min(per_slot, _pow2(-(-tokens // page)))

    shapes = {(_pow2(w), view(w)) for w in range(min(lo, C), min(C, hi) + 1)}
    widths = [1]
    while widths[-1] < _pow2(C):
        widths.append(widths[-1] * 2)
    v = view(min(C, lo) + 1)
    while True:
        shapes.update((t, v) for t in (widths if v >= view(C + 1) else [1]))
        if v >= view(max_len):
            break
        v = view(v * page + 1)
    return sorted(shapes)


def prompt_range(traffic: dict) -> tuple[int, int]:
    if "prompt" in traffic:
        return traffic["prompt"]["min"], traffic["prompt"]["max"]
    return (traffic["document"]["min"] + traffic["question"]["min"],
            traffic["document"]["max"] + traffic["question"]["max"])


def warm_up(engine, traffic: dict, vocab: int) -> int:
    """Compile or load every step program the window can use: one call of
    the engine's step per (T, page view) shape on an idle pool, then one
    request through submit/step/cancel, and for a mix with shared
    prefixes one cache hit (the copy-on-write program).  Returns the
    number of step shapes."""
    B = traffic["engine"]["slots"]
    shapes = warm_shapes(traffic)
    for T, mp in shapes:
        engine._exec_step(np.zeros((B, T), np.int32), np.zeros(B, np.int32),
                          mp)
    rng = np.random.default_rng(0)
    lo, _ = prompt_range(traffic)
    rid = engine.submit(rng.integers(0, vocab, lo).astype(np.int32))
    while engine.pending() and _generated(engine, rid) < 2:
        engine.step()
    engine.cancel(rid)
    if "document" in traffic:
        doc = rng.integers(0, vocab, traffic["document"]["min"] + 5)
        for _ in range(2):
            q = rng.integers(0, vocab, traffic["question"]["min"])
            engine.submit(np.concatenate([doc, q]).astype(np.int32))
            while engine.pending():
                engine.step()
    engine.reset_serving_state()
    return len(shapes)


def _generated(engine, rid) -> int:
    if rid in engine.results:
        return len(engine.results[rid])
    for r in engine.sched.active():
        if r.rid == rid:
            return r.n_generated
    return 0


# ---------------------------------------------------------------------------
# work done between two moments of the window
# ---------------------------------------------------------------------------

class Interval:
    """The engine's own counters at the interval's two ends, as
    differences: ``traffic_stats`` (tokens whose KV the steps wrote, keys
    each row read) and ``prefix_stats`` (prompt tokens the prefix cache
    matched).  Beside them, what those counters lack, tallied from the
    tokens each request emitted: the decode rows (a request's every token
    after its first) and the keys each attended.  Decode rows run in the
    engine's single-token call, which takes the paged decode kernel."""

    def __init__(self):
        self.start = self.end = None
        self.emitted = 0
        self.decode_rows = 0
        self.decode_keys = 0

    @staticmethod
    def counters(engine) -> dict:
        tr, px = engine.traffic_stats(), engine.prefix_stats()
        return {"written": tr["written_tokens"], "read": tr["gb_read_tokens"],
                "matched": px.get("matched_tokens", 0),
                "lookups": px.get("lookups", 0)}

    @property
    def open(self) -> bool:
        return self.start is not None and self.end is None

    def begin(self, engine) -> None:
        self.start = self.counters(engine)

    def close(self, engine) -> None:
        self.end = self.counters(engine)

    def emit(self, r, n: int) -> None:
        """Request ``r`` went from ``r.n`` emitted tokens to ``n``: token
        k + 1 (k >= 1) came from a decode row fed token k, which attended
        the prompt and k tokens."""
        self.emitted += n - r.n
        for k in range(max(r.n, 1), n):
            self.decode_rows += 1
            self.decode_keys += len(r.prompt) + k

    def _delta(self, key: str) -> int:
        return self.end[key] - self.start[key]

    @property
    def computed(self) -> int:
        """Tokens the steps computed: prompt positions and decode rows."""
        return self._delta("written")

    @property
    def keys_read(self) -> int:
        """Keys the computed rows read, one read of its context a row."""
        return self._delta("read")

    @property
    def prompt_computed(self) -> int:
        return self.computed - self.decode_rows

    @property
    def matched(self) -> int:
        return self._delta("matched")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def setup(cell, seed: int, trace: bool, log, adapter=None):
    """Weights from the seed, the engine, and every program the window can
    use.  Returns (family, adapter, params, engine, telemetry or None);
    an ``adapter`` from an earlier setup in this process keeps its jitted
    steps."""
    from repro.serving import ServeConfig, ServingEngine

    fam = cell.family()
    obs = None
    if trace:
        import repro.obs as robs
        obs = robs.enable(process_name="bench")
    if adapter is None:
        adapter = fam.ServingAdapter(fam.program_bundle(cell.config))
    params = fam.make_params(adapter.bundle, harness.jax_key(seed))
    eng = cell.traffic["engine"]
    scfg = ServeConfig(batch=eng["slots"], max_len=eng["max_len"],
                       max_new_tokens=eng["max_new_tokens"],
                       page_size=eng["page_size"], num_pages=eng["num_pages"],
                       prefill_chunk=eng["prefill_chunk"],
                       prefill_token_budget=eng["prefill_token_budget"],
                       prefix_cache=eng["prefix_cache"], kv_mode="paged")
    engine = ServingEngine(adapter, params, scfg)
    n_warm = warm_up(engine, cell.traffic, adapter.cfg.vocab)
    log(f"[setup] warmed {n_warm} step shapes")
    return fam, adapter, params, engine, obs


def window(cell, engine, seed: int, seconds: float, trace: bool, obs, log,
           traffic: dict | None = None) -> dict:
    """Offer the mix's load for ``seconds`` (and drain an open loop's
    window); returns the requests and what was measured on them."""
    traffic = traffic or cell.traffic
    vocab = engine.bundle.cfg.vocab
    closed = traffic["loop"] == "closed"
    if closed:
        queue = sessions(traffic, vocab, seed)
        plan = []
    else:
        plan = open_loop_plan(traffic, seconds, vocab, seed)
    counter = harness.CompileCounter()
    ann = harness.annotator(trace)
    traced = Interval()
    served = Interval()              # ticks that begin inside the window
    itl: list[float] = []
    prof = harness.Profiler(cell.root, cell.name) if trace else None
    trace_at = seconds / 3.0
    trace_len = min(traffic.get("trace_seconds", 4.0), seconds / 3.0)
    clock = time.monotonic
    live: dict[int, Req] = {}

    def submit(r, now):
        with ann("submit"):
            r.rid = engine.submit(r.prompt)
        r.submitted = now
        live[r.rid] = r

    def harvest(t, in_window):
        counts = {q.rid: q.n_generated for q in engine.sched.active()}
        for rid in list(live):
            r = live[rid]
            n = len(engine.results[rid]) if rid in engine.results \
                else counts.get(rid, r.n)
            if n > r.n:
                if in_window:
                    served.emit(r, n)
                if traced.open:
                    traced.emit(r, n)
                if t <= seconds and r.last_t is not None and n == r.n + 1:
                    itl.append(t - r.last_t)
                if r.first_t is None:
                    r.first_t = t
                r.n, r.last_t = n, t
            if rid in engine.results or n >= r.out_len:
                if rid not in engine.results:
                    engine.cancel(rid)
                r.outcome = engine.outcomes.get(rid)
                r.served = np.asarray(engine.results[rid], np.int32)
                r.done_t = t
                del live[rid]
                if closed and t < seconds:
                    next_ask(r.client, t)

    clients = {}

    def next_ask(client, t):
        c = clients.setdefault(client, {"session": None, "i": 0})
        if c["session"] is None or c["i"] >= len(c["session"]):
            c["session"], c["i"] = next(queue), 0
        r = Req(c["session"][c["i"]], traffic["output"]["value"], t, True,
                client)
        c["i"] += 1
        plan.append(r)
        submit(r, t)

    t0 = clock()
    now = lambda: clock() - t0          # noqa: E731
    counter.armed = True
    served.begin(engine)
    if closed:
        for c in range(traffic["clients"]):
            next_ask(c, 0.0)
    pending = [] if closed else plan[::-1]
    while True:
        t = now()
        if prof is not None and not prof.active and prof.t0 is None \
                and t >= trace_at:
            prof.start(now)
            traced.begin(engine)
        if prof is not None and prof.active and t >= trace_at + trace_len:
            traced.close(engine)
            prof.stop(now)
        while pending and pending[-1].due <= t:
            submit(pending.pop(), t)
        if closed:
            if t >= seconds:
                break
        else:
            if t >= seconds and not any(r.measured and r.first_t is None
                                        for r in live.values()) \
                    and not any(r.measured for r in pending):
                break
            if t >= seconds + traffic["drain_max_s"]:
                break
        if engine.pending():
            if t >= seconds and served.open:
                served.close(engine)
            with ann("engine.step"):
                engine.step()
            with ann("harvest"):
                harvest(now(), t < seconds)
        elif pending:
            time.sleep(max(0.0, min(pending[-1].due - now(), 0.05)))
        elif not live:
            break
    t_end = now()
    counter.armed = False
    if served.open:
        served.close(engine)
    log(f"[window] compilations inside the window: {counter.count} "
        f"({counter.seconds:.3f} s)")

    # open loop: every request due in the window; closed loop: every ask
    # that ended in the window.  A request still decoding when the run
    # ends has neither completed nor failed; one that was shed, timed
    # out, or never had its first token has failed.
    measured = [r for r in plan if r.measured
                and (not closed or r.done_t is not None)]
    completed = [r for r in measured if r.done_t is not None
                 and r.outcome in ("ok", "cancelled")]
    failed = [r for r in measured if r.first_t is None or
              (r.done_t is not None and r.outcome not in ("ok", "cancelled"))]
    lateness = [r.submitted - r.due for r in measured
                if r.submitted is not None]
    out = {"completed": completed,
           "attempted": len(measured), "failed": len(failed),
           "compiles": counter.count, "t_end": t_end,
           "in_flight": len(live),
           "tokens_per_s": (served.prompt_computed + served.matched
                            + served.emitted) / seconds,
           "itl_ms": [1e3 * g for g in itl],
           "ttft_ms": [1e3 * ((r.first_t if r.first_t is not None
                               else t_end) - r.due) for r in measured],
           "tpot_ms": [1e3 * (r.done_t - r.first_t) / (r.n - 1)
                       for r in completed if r.n > 1]}
    log(f"[window] {out['attempted']} requests due, {len(completed)} "
        f"completed, {out['failed']} failed, {len(live)} in flight at the "
        f"end; generator lateness mean "
        f"{1e3 * float(np.mean(lateness)) if lateness else 0:.2f} ms, max "
        f"{1e3 * max(lateness, default=0):.2f} ms; ran {t_end:.2f} s")
    for k in ("ttft_ms", "tpot_ms", "itl_ms"):
        if out[k]:
            q = [harness.percentile(out[k], p) for p in (50, 90, 95, 99)]
            log(f"[window] {k} p50 {q[0]:.2f} p90 {q[1]:.2f} p95 {q[2]:.2f}"
                f" p99 {q[3]:.2f} ({len(out[k])} samples)")
    log(f"[window] served {out['tokens_per_s']:.1f} tokens/s (prefilled "
        f"{served.prompt_computed}, from the prefix cache {served.matched}, "
        f"emitted {served.emitted}, decode rows {served.decode_rows}; "
        f"{served._delta('lookups')} prefix lookups)")
    if prof is not None and prof.t0 is not None:
        out["layer_ctx"] = {
            "traced": traced, "served": served, "xplane": prof.xplane(),
            "spans": harness.spans_between(obs, t0 + prof.t0,
                                           t0 + prof.t1)}
    return out


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, log) -> tuple:
    """One serving run; returns (metrics, attempted, failed, checks,
    device, per-layer context or None)."""
    fam, adapter, params, engine, obs = setup(cell, seed, trace, log)
    setup_s = time.monotonic() - t_start
    w = window(cell, engine, seed, seconds, trace, obs, log)
    dev = harness.device_info(devices)

    metrics = {"setup_s": setup_s,
               "serve_tokens_per_s": w["tokens_per_s"]}
    if w["ttft_ms"]:
        metrics["ttft_p50_ms"] = harness.percentile(w["ttft_ms"], 50)
    if w["itl_ms"]:
        metrics["itl_p95_ms"] = harness.percentile(w["itl_ms"], 95)
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    metrics = {k: {"value": v, "unit": units[k]}
               for k, v in metrics.items() if k in units}

    layer_ctx = w.get("layer_ctx")
    if layer_ctx is not None:
        layer_ctx.update(config=cell.config, family=fam,
                         traffic=cell.traffic)

    # correctness: the program's state goes first, then the reference
    sample = _sample(w["completed"], cell.traffic, seed)
    attempted, failed = w["attempted"], w["failed"]
    del engine, w
    gc.collect()
    checks = check_sample(cell, params, sample, log)
    return metrics, attempted, failed, checks, dev, layer_ctx


def _sample(completed, traffic, seed):
    """A sample of finished requests drawn from the seed, holding the
    longest (prompt plus served tokens)."""
    k = traffic["check"]["sample_requests"]
    if not completed:
        return []
    longest = max(completed, key=lambda r: len(r.prompt) + len(r.served))
    rest = [r for r in completed if r is not longest]
    rng = np.random.default_rng(seed + 1)
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def check_sample(cell, params, sample, log) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over the sample, against the mix's limit."""
    limit = cell.traffic["check"]["max_logit_gap"]
    if not sample:
        log("[check] no finished request to compare")
        return {"max_logit_gap": {"value": None, "limit": limit},
                "served_tokens_compared": {"value": 0, "limit": 1}}
    ref = cell.reference()
    t = time.monotonic()
    gaps = ref.served_gaps(cell.config, params,
                           [(r.prompt, r.served) for r in sample])
    worst = float(max(g.max() for g in gaps))
    n = int(sum(len(g) for g in gaps))
    log(f"[check] reference over {len(sample)} requests "
        f"({n} served tokens, longest {len(sample[0].prompt)} + "
        f"{len(sample[0].served)}) took {time.monotonic() - t:.1f} s")
    return {"max_logit_gap": {"value": worst, "limit": limit},
            "served_tokens_compared": {"value": n, "limit": 1}}


def checks_pass(checks: dict) -> bool:
    g = checks["max_logit_gap"]
    n = checks["served_tokens_compared"]
    return (g["value"] is not None and math.isfinite(g["value"])
            and g["value"] <= g["limit"] and n["value"] >= n["limit"])
