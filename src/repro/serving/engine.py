"""Serving engine: continuous batching over a dense OR paged KV cache.

Two KV modes behind one interface (``ServeConfig.kv_mode``):

``dense``
    The seed path, kept for tests and as the benchmark baseline: a fixed
    pool of ``batch`` slots, each reserving ``max_len`` KV up front;
    decode ticks run the whole pool (one jitted SPMD step regardless of
    occupancy).  Two seed inefficiencies are fixed here: prefill is JITTED
    with length-BUCKETED padding (power-of-two buckets + ``true_lengths``,
    so repeated admissions hit a handful of traces instead of retracing
    per prompt length), and the single-slot prefill cache template is
    allocated ONCE instead of per admission.  Slot writes are driven by
    the bundle's declared per-entry batch axes (``cache_batch_axes``)
    instead of a hardwired (L, B, ...) assumption.

``paged`` / ``paged_int8``
    The block-pool path, now a CONTINUOUS-BATCHING front-end: K/V live in
    fixed-size refcounted pages allocated from a global pool
    (``serving.kv.BlockPoolKV``), a radix prefix cache
    (``serving.prefix.RadixPrefixCache``, on by default) deduplicates
    shared prompt prefixes across requests — admission maps matched pages
    read-only, copy-on-write covers mid-page divergence, and prefill
    covers only the unmatched suffix — and the phase-aware scheduler
    (``serving.scheduler.PhaseScheduler``) admits/evicts PER TICK.  Each
    tick runs jitted ``paged_step`` over the pool's active rows grouped
    by padded length — wide prefill chunks in one call, decode rows and
    single-token cache-hit suffixes together in a ``T == 1`` call — so
    rows join and leave freely: a row may be mid-prefill (a chunk of
    ``counts[b]`` tokens) while its neighbours decode one token each — no
    phase epochs, no prefill convoy.  The per-row next-token gather and
    greedy argmax ride INSIDE the jitted step (one dispatch per call;
    host-side gathers dominate tick time otherwise).  The page-table
    view is sliced to a
    power-of-two page bucket covering the longest ACTIVE slot so compute
    and resident KV bytes scale with real sequence lengths, not
    ``batch x max_len``.  ``paged_int8`` keeps the pool quantized with
    per-(token, head) scale tables.

The engine's loop is exposed three ways: :meth:`run` drains everything
(the batch API), :meth:`step`/:meth:`pending` advance one tick (the
event-loop API the traffic benchmark drives), and :meth:`stream` returns
a per-request token GENERATOR that pulls ticks on demand — cooperative
streaming without threads, so interleaved consumers each see their tokens
the tick they are produced.

Sampling: greedy by default (``temperature == 0``); ``temperature`` plus
optional ``top_k`` switch decode to seeded host-side softmax sampling
(``sample_seed`` makes traces replayable).  Caches and steps follow
``repro.parallel.sharding`` (``paged_pool_specs`` for the pool); the
engine itself is host-side control logic and is exercised on CPU in tests.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import get_telemetry

from .kv import BlockPoolKV, PagedKVConfig
from .prefix import RadixPrefixCache
from .scheduler import Phase, PhaseScheduler, Request, SchedulerConfig

KV_MODES = ("dense", "paged", "paged_int8")
# the token counters of ``ServingEngine.traffic_stats``
_TRAFFIC_COUNTERS = ("gb_read_tokens", "dram_read_tokens", "written_tokens",
                     "prefill_tokens", "decode_rows", "decode_keys")


class _TracedPrefix:
    """Engine-side proxy around the radix prefix cache: times ``match``
    as a ``prefix_match`` span (with hit/matched-token args) without the
    jax-free scheduler/prefix modules ever importing telemetry.  Every
    other attribute forwards to the wrapped cache."""

    def __init__(self, prefix: RadixPrefixCache, obs):
        self._prefix = prefix
        self._obs = obs

    def match(self, tokens):
        h = self._obs.begin("prefix_match", tokens=int(len(tokens)))
        m = self._prefix.match(tokens)
        self._obs.finish(h, matched=int(m.matched), hit=bool(m.hit))
        return m

    def __getattr__(self, name):
        return getattr(self._prefix, name)


@dataclasses.dataclass
class ServeConfig:
    batch: int              # slot pool size
    max_len: int
    max_new_tokens: int = 32
    eos_id: int = -1        # -1: never stop early
    temperature: float = 0.0        # 0: greedy; > 0: sampled decode
    top_k: int = 0                  # 0: full vocab; else sample top-k only
    sample_seed: int = 0            # host RNG seed (deterministic traces)
    kv_mode: str = "dense"          # dense | paged | paged_int8
    page_size: int = 16             # paged: tokens per page
    num_pages: int | None = None    # paged: pool size (None = dense capacity)
    prefill_chunk: int = 32         # paged: tokens per prefill call
    prefill_token_budget: int = 64  # paged: prefill tokens per tick
    prefix_cache: bool = True       # paged: radix prefix sharing + COW
    min_prefill_bucket: int = 8     # dense: smallest padded prompt bucket
    # graceful degradation (all off by default = seed behaviour):
    max_admission_retries: int = 0  # shed a request after N failed admits
    admission_backoff: int = 0      # base hold-off ticks between admits
    shed_pressure: float = 1.0      # pool-used fraction counted as critical
    shed_patience: int = 0          # critical ticks before load-shed (0=off)
    shed_min_priority: int = 1      # load-shed drops waiting prio < this


@dataclasses.dataclass
class _Slot:
    request_id: int | None = None
    generated: list[int] = dataclasses.field(default_factory=list)
    remaining: int = 0
    deadline_tick: int | None = None


def _pow2_at_least(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


# Module-level jits shared by every engine instance (a per-engine closure
# would give each engine its own compile cache, so benchmarks/tests that
# build fresh engines over the same bundle would re-trace identical
# shapes).  ``step`` is the bundle's paged_step, static so its identity
# keys the cache.  Each donates the pool it is given, so a step or a page
# copy updates the pool in place instead of writing a second one; the
# engine rebinds ``self.pool`` to the result and nothing else may hold the
# old arrays.
@functools.partial(jax.jit, donate_argnums=0)
def _copy_pool_page(pool, src, dst):
    """COW: copy one physical page (all layers, K+V+scales).  The page
    ids ride as traced scalars so every copy reuses one trace."""
    return {k: v.at[:, dst].set(v[:, src]) for k, v in pool.items()}


@functools.partial(jax.jit, donate_argnums=0)
def _write_pool_page(pool, dst, vals):
    """Land a MIGRATED page's payload (all layers, K+V+scales) at
    physical page ``dst`` — the receive half of fleet page migration.
    One trace serves every import: ``dst`` rides traced."""
    return {k: v.at[:, dst].set(vals[k].astype(v.dtype))
            for k, v in pool.items()}


def _pick_step(step, params, tokens, pool, pt, lens, counts):
    """paged_step plus the per-row next-token gather (each row's logits
    sit at ``counts[b] - 1``) and the greedy argmax, fused into ONE
    jitted dispatch — doing the gather outside jit costs more host time
    per tick than the step itself on small models."""
    logits, pool, _ = step(params, tokens, pool, pt, lens, counts)
    idx = jnp.maximum(counts, 1)[:, None, None] - 1
    rows = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
    return rows, jnp.argmax(rows, axis=-1), pool


_pick_step = jax.jit(_pick_step, static_argnums=0, donate_argnums=3)


class ServingEngine:
    """bundle must provide: init_cache(batch, max_len), prefill(params,
    tokens, cache, **extras), decode_step(params, tokens, cache); the paged
    modes additionally need init_paged_pool / paged_step /
    supports_paged_kv (the transformer family; see configs/base.py)."""

    # consecutive ticks with work queued but nothing executed before the
    # engine declares the scheduler wedged (admission backoff can idle a
    # bounded run of ticks legitimately)
    STALL_LIMIT = 4096

    def __init__(self, bundle: Any, params: Any, cfg: ServeConfig,
                 mesh: Any = None, telemetry: Any = None):
        if cfg.kv_mode not in KV_MODES:
            raise ValueError(f"kv_mode {cfg.kv_mode!r} not in {KV_MODES}")
        self.bundle = bundle
        self.params = params
        self.cfg = cfg
        self.mesh = mesh               # concrete Mesh: shard the page pool
        # telemetry: explicit Telemetry, or the process global (disabled
        # unless a launcher/bench called ``obs.enable()``)
        self.obs = telemetry if telemetry is not None else get_telemetry()
        self.results: dict[int, list[int]] = {}
        self.outcomes: dict[int, str] = {}   # rid -> ok | timeout | shed
        self._next_id = 0
        self._pressure_ticks = 0             # consecutive critical ticks
        self._shed_mode_ticks = 0
        self._stall_ticks = 0
        self._rng = np.random.default_rng(cfg.sample_seed)
        if cfg.kv_mode == "dense":
            self._init_dense()
        else:
            self._init_paged()

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------

    @property
    def _greedy(self) -> bool:
        return self.cfg.temperature <= 0.0

    def _pick(self, row) -> int:
        """Next token from one vocab-sized logit row (jax or numpy).

        Greedy at ``temperature == 0`` (the default — deterministic
        traces for tests/benchmarks; argmax stays on device so only a
        scalar crosses to the host); otherwise temperature-scaled
        softmax sampling, optionally restricted to the ``top_k``
        highest-logit tokens.  Sampling happens host-side from the
        engine's seeded RNG: only ACTIVE slots draw (in slot order), so
        a given (seed, trace) pair always replays the same tokens."""
        cfg = self.cfg
        if self._greedy:
            return int(jnp.argmax(row))
        z = np.asarray(row, np.float64) / cfg.temperature
        if 0 < cfg.top_k < z.size:
            kth = np.partition(z, -cfg.top_k)[-cfg.top_k]
            z = np.where(z >= kth, z, -np.inf)
        z = z - z.max()
        p = np.exp(z)
        return int(self._rng.choice(z.size, p=p / p.sum()))

    # ------------------------------------------------------------------
    # intake + the three loop surfaces (run / step / stream)
    # ------------------------------------------------------------------

    def submit(self, prompt_tokens: np.ndarray, priority: int = 0,
               deadline: int | None = None) -> int:
        """Queue a request.  ``priority`` (larger = more urgent) drives
        paged admission/preemption; the dense path keeps seed FIFO.
        ``deadline`` is a tick budget counted from NOW: a request still
        unfinished after that many engine ticks is evicted with whatever
        it has generated (``outcomes[rid] == "timeout"``)."""
        rid = self._next_id
        self._next_id += 1
        prompt = np.asarray(prompt_tokens, np.int32)
        total = len(prompt) + self.cfg.max_new_tokens
        if total > self.cfg.max_len:
            # dense would silently clamp cache writes at max_len-1 and
            # corrupt tokens; paged could deadlock admission — reject both
            raise ValueError(f"request {rid}: prompt+max_new {total} "
                             f"exceeds max_len {self.cfg.max_len}")
        if self.cfg.kv_mode == "dense":
            dl = None if deadline is None else self._dense_tick + deadline
            self.queue.append((rid, prompt, priority, dl))
            return rid
        req = Request(rid=rid, prompt=prompt, priority=priority,
                      arrival=rid, max_new_tokens=self.cfg.max_new_tokens,
                      deadline_tick=None if deadline is None
                      else self.ticks + deadline)
        need = self.kv.pages_for(total) + 1     # +1 decode headroom
        if need > self.kv.cfg.total_pages - 1:
            raise ValueError(f"request {rid}: needs {need} pages, pool has "
                             f"{self.kv.cfg.total_pages - 1}")
        self._requests[rid] = req
        self.sched.submit(req)
        return rid

    def reset_serving_state(self) -> None:
        """Drop all serving state — pool, scheduler, prefix trie, results,
        tick/pressure counters — while KEEPING the engine's compiled jit
        traces (they are keyed on the bundle, which survives the reset).
        Benchmarks use this to absorb compilation in an unmeasured warm
        pass and then measure a genuinely cold-cache serve: a fresh
        engine would re-trace every shape, a reset one does not."""
        self.results = {}
        self.outcomes = {}
        self._pressure_ticks = 0
        self._shed_mode_ticks = 0
        self._stall_ticks = 0
        self._rng = np.random.default_rng(self.cfg.sample_seed)
        if self.cfg.kv_mode == "dense":
            self._init_dense()
        else:
            self._init_paged()

    def pending(self) -> bool:
        """Whether any submitted request is still queued or in flight."""
        if self.cfg.kv_mode == "dense":
            return bool(self.queue) or \
                any(s.request_id is not None for s in self.slots)
        return self.sched.has_work

    def step(self) -> None:
        """Advance the engine ONE tick: expire deadlines, admit/evict,
        then one device step over the whole slot pool (prefill chunks and
        decode rows share it in the paged modes).  The event-loop API —
        callers interleave ``submit`` and ``step`` to serve an open-ended
        arrival stream (see benchmarks/bench_traffic.py)."""
        if self.cfg.kv_mode == "dense":
            self._step_dense()
        else:
            self._step_paged()

    def run(self, cache=None) -> dict[int, list[int]]:
        """Drain every queued/active request to completion."""
        if self.cfg.kv_mode == "dense" and cache is not None:
            self._dense_cache = cache
        while self.pending():
            self.step()
        return self.results

    def stream(self, rid: int) -> Iterator[int]:
        """Per-request token generator: yields ``rid``'s tokens as the
        continuous-batching loop produces them, driving :meth:`step` on
        demand when no new tokens are buffered.  Multiple streams
        interleave cooperatively — each tick's tokens are visible to
        every consumer immediately."""
        sent = 0
        while True:
            done = rid in self.results
            toks = self.results[rid] if done else self._partial_output(rid)
            while sent < len(toks):
                yield toks[sent]
                sent += 1
            if done:
                return
            if not self.pending():      # rid unknown / already reaped
                return
            self.step()

    def _partial_output(self, rid: int) -> list[int]:
        if self.cfg.kv_mode == "dense":
            for s in self.slots:
                if s.request_id == rid:
                    return list(s.generated)
            return []
        req = self._requests.get(rid)
        return req.output if req is not None else []

    # ------------------------------------------------------------------
    # dense path (seed behaviour + bucketed-jit prefill + declared axes)
    # ------------------------------------------------------------------

    def _init_dense(self) -> None:
        cfg = self.cfg
        self.slots = [_Slot() for _ in range(cfg.batch)]
        self.queue: list[tuple[int, np.ndarray, int, int | None]] = []
        self._dense_tick = 0
        self._dense_cache = None
        self._traffic = dict.fromkeys(_TRAFFIC_COUNTERS, 0)
        self._decode = jax.jit(self.bundle.decode_step)
        self._cache_axes: dict | None = None
        self._prefill_template = None       # built lazily, reused forever
        self._bucketed = bool(getattr(self.bundle,
                                      "prefill_supports_true_lengths", False))
        if self._bucketed:
            self._prefill = jax.jit(
                lambda p, t, c, tl: self.bundle.prefill(p, t, c,
                                                        true_lengths=tl))
        else:
            # exact-length fallback (families whose caches cannot absorb
            # padded prompts, e.g. SSM states): still jitted — repeated
            # admissions of the same prompt length reuse one trace — and
            # still template-reusing.
            self._prefill = jax.jit(
                lambda p, t, c: self.bundle.prefill(p, t, c))

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.request_id is None]

    def _prompt_bucket(self, n: int) -> int:
        return min(self.cfg.max_len,
                   _pow2_at_least(n, self.cfg.min_prefill_bucket))

    def _admit(self, cache):
        """Prefill queued requests into free slots (per-slot batch=1,
        length-bucketed so admissions reuse a handful of jit traces)."""
        for slot_idx in self._free_slots():
            if not self.queue:
                break
            rid, prompt, _, deadline = self.queue.pop(0)
            if self._prefill_template is None:
                self._prefill_template = self.bundle.init_cache(
                    1, self.cfg.max_len)
            toks = jnp.asarray(prompt, jnp.int32)[None]
            S = toks.shape[1]
            with self.obs.span("prefill", rid=rid, tokens=int(S)):
                if self._bucketed:
                    Sb = self._prompt_bucket(S)
                    toks = jnp.pad(toks, ((0, 0), (0, Sb - S)))
                    logits, c1 = self._prefill(
                        self.params, toks, self._prefill_template,
                        jnp.asarray([S], jnp.int32))
                else:
                    logits, c1 = self._prefill(self.params, toks,
                                               self._prefill_template)
            nxt = self._pick(logits[0, -1])
            cache = self._write_slot(cache, c1, slot_idx)
            s = self.slots[slot_idx]
            s.request_id = rid
            s.generated = [nxt]
            s.remaining = self.cfg.max_new_tokens - 1
            s.deadline_tick = deadline
        return cache

    def _write_slot(self, cache, one, idx):
        """Copy a batch=1 cache into slot ``idx`` of the pooled cache.

        The batch axis of each entry comes from the bundle's declared
        layout (``cache_batch_axes``) — e.g. recurrentgemma's grouped
        recurrent states carry batch at axis 2 — with the seed's
        axis-0-for-1D / axis-1-otherwise rule as the fallback for bundles
        that declare nothing."""
        if self._cache_axes is None:
            declare = getattr(self.bundle, "cache_batch_axes", None)
            if declare is not None:
                self._cache_axes = dict(declare(cache))
            else:
                self._cache_axes = {k: 0 if v.ndim == 1 else 1
                                    for k, v in cache.items()}
        out = {}
        for k, v in cache.items():
            ax = self._cache_axes[k]
            start = (0,) * ax + (idx,) + (0,) * (v.ndim - ax - 1)
            out[k] = jax.lax.dynamic_update_slice(
                v, one[k].astype(v.dtype), start)
        return out

    def _expire_dense(self) -> None:
        """Timeout eviction, dense flavour: queued requests past deadline
        never start; decoding slots past deadline free up with whatever
        they generated."""
        now = self._dense_tick
        kept = []
        for rid, prompt, prio, dl in self.queue:
            if dl is not None and now >= dl:
                self.results[rid] = []
                self.outcomes[rid] = "timeout"
            else:
                kept.append((rid, prompt, prio, dl))
        self.queue = kept
        for i, s in enumerate(self.slots):
            if s.request_id is not None and s.deadline_tick is not None \
                    and now >= s.deadline_tick:
                self.results[s.request_id] = s.generated
                self.outcomes[s.request_id] = "timeout"
                self.slots[i] = _Slot()

    def _step_dense(self) -> None:
        cfg = self.cfg
        if self._dense_cache is None:
            self._dense_cache = self.bundle.init_cache(cfg.batch, cfg.max_len)
        self._dense_tick += 1
        obs = self.obs
        self._expire_dense()
        with obs.span("admission", tick=self._dense_tick):
            self._dense_cache = self._admit(self._dense_cache)
        if not any(s.request_id is not None for s in self.slots):
            return
        with obs.span("decode", tick=self._dense_tick):
            # one decode tick for the whole pool
            last = np.zeros((cfg.batch, 1), np.int32)
            for i, s in enumerate(self.slots):
                if s.request_id is not None:
                    last[i, 0] = s.generated[-1]
            logits, self._dense_cache = self._decode(
                self.params, jnp.asarray(last), self._dense_cache)
            # greedy: batch argmax on device, ints cross to host; sampled:
            # one host copy of the active rows feeds the seeded picker
            nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1)) \
                if self._greedy else np.asarray(logits[:, 0])
        for i, s in enumerate(self.slots):
            if s.request_id is None:
                continue
            tok = int(nxt[i]) if self._greedy else self._pick(nxt[i])
            s.generated.append(tok)
            s.remaining -= 1
            if s.remaining <= 0 or tok == cfg.eos_id:
                self.results[s.request_id] = s.generated
                self.outcomes[s.request_id] = "ok"
                obs.counter("serve_requests", outcome="ok")
                obs.instant("complete", rid=s.request_id,
                            generated=len(s.generated))
                self.slots[i] = _Slot()

    # ------------------------------------------------------------------
    # paged path (block pool + prefix cache + continuous batching)
    # ------------------------------------------------------------------

    def _init_paged(self) -> None:
        cfg = self.cfg
        if not getattr(self.bundle, "supports_paged_kv", False):
            raise ValueError("bundle does not support the paged KV path "
                             "(needs init_paged_pool/paged_step)")
        mcfg = self.bundle.cfg
        quant = cfg.kv_mode == "paged_int8"
        kv_dtype = jnp.int8 if quant else None
        kv_bytes = 1 if quant else jnp.dtype(mcfg.dtype).itemsize
        pages_per_slot = -(-cfg.max_len // cfg.page_size)
        num_pages = cfg.num_pages or cfg.batch * pages_per_slot + 1
        if self.mesh is not None:
            # the page axis shards over the data axes — round the pool up
            # so every shard gets whole pages (same axis inventory the
            # pool specs use, so rounding and sharding can't diverge)
            from repro.parallel.sharding import _data_axes
            dsz = 1
            for a in _data_axes(self.mesh):
                dsz *= self.mesh.shape[a]
            num_pages = -(-num_pages // dsz) * dsz
        self.kv = BlockPoolKV(PagedKVConfig(
            num_slots=cfg.batch, max_len=cfg.max_len,
            page_size=cfg.page_size, num_pages=num_pages,
            n_layers=mcfg.n_layers, kv_heads=mcfg.n_kv_heads,
            head_dim=mcfg.dh, kv_bytes=kv_bytes, quantize=quant))
        # the radix prefix cache registers itself as the pool's reclaim
        # hook: page pressure drains cold cached prefixes before anyone
        # preempts a live request
        self.prefix = RadixPrefixCache(self.kv) if cfg.prefix_cache else None
        self.sched = PhaseScheduler(SchedulerConfig(
            num_slots=cfg.batch, prefill_chunk=cfg.prefill_chunk,
            prefill_token_budget=cfg.prefill_token_budget,
            max_admission_retries=cfg.max_admission_retries,
            admission_backoff=cfg.admission_backoff))
        self.pool = self.bundle.init_paged_pool(num_pages, cfg.page_size,
                                                kv_dtype=kv_dtype)
        if self.mesh is not None:
            # pool lives across the mesh: page axis over data, head
            # structure over model (repro.parallel.sharding)
            from repro.parallel.sharding import paged_pool_specs
            specs = paged_pool_specs(self.mesh, kv_heads=mcfg.n_kv_heads,
                                     head_dim=mcfg.dh)
            self.pool = {
                k: jax.device_put(
                    v, jax.sharding.NamedSharding(self.mesh, specs[k]))
                for k, v in self.pool.items()}
        self._requests: dict[int, Request] = {}
        self.cow_copies = 0
        self.ticks = 0
        # live KV traffic: token-exact attended context (the paper's
        # global-buffer level) and page-granular pool reads (DRAM level),
        # accumulated in _exec_rows.  Plain int adds — always on; the
        # roofline accountant compares them against the closed-form
        # prediction (obs.roofline_live.predict_paged_decode_traffic).
        self._traffic = dict.fromkeys(_TRAFFIC_COUNTERS, 0)

    def _pages_view(self, max_tokens: int) -> int:
        """Power-of-two page-table slice covering ``max_tokens`` — the
        static shape buckets that let gather/attention cost track actual
        lengths while reusing a log number of jit traces."""
        per_slot = self.kv.cfg.pages_per_slot
        return min(per_slot, _pow2_at_least(self.kv.pages_for(max_tokens)))

    def _mesh_ctx(self):
        from repro.runtime import compat
        return compat.set_mesh(self.mesh) if self.mesh is not None else None

    def _exec_step(self, tokens: np.ndarray, counts: np.ndarray, mp: int):
        """Run one jitted paged_step + row-gather + argmax over the whole
        slot pool (inside the ambient mesh context when the pool is
        sharded, so paged_step's sharding constraints resolve).  Returns
        ``(rows, picked)``: each slot's next-token logits and their
        argmax, both still on device."""
        pt = self.kv.page_table[:, :mp]
        lens = self.kv.lengths.astype(np.int32)
        ctx = self._mesh_ctx()
        try:
            if ctx is not None:
                ctx.__enter__()
            rows, picked, self.pool = _pick_step(
                self.bundle.paged_step, self.params, tokens, self.pool,
                pt, lens, counts.astype(np.int32))
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
        return rows, picked

    def _exec_cow(self, req: Request) -> None:
        """Execute a pending copy-on-write: duplicate the matched page's
        KV into the request's first private page, then release the pin
        admission held on the source."""
        src, dst, _ = req.cow
        ctx = self._mesh_ctx()
        try:
            if ctx is not None:
                ctx.__enter__()
            self.pool = _copy_pool_page(self.pool,
                                        jnp.asarray(src, jnp.int32),
                                        jnp.asarray(dst, jnp.int32))
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
        self.cow_copies += 1
        self.sched._drop_cow(self.kv, req)

    def _finish(self, req: Request) -> None:
        """Reap a completed request: adopt its cached pages into the
        prefix trie (they outlive the request until page pressure evicts
        them leaf-first), then release the slot."""
        if self.prefix is not None:
            n_cached = int(self.kv.lengths[req.slot])
            seq = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)])[:n_cached]
            self.prefix.insert(seq, self.kv.slot_pages(req.slot), n_cached)
        self.results[req.rid] = req.output
        self.outcomes[req.rid] = "ok"
        self.obs.counter("serve_requests", outcome="ok")
        self.obs.instant("complete", rid=req.rid,
                         generated=req.n_generated)
        self.sched.finish(self.kv, req)

    def _degrade_tick(self) -> None:
        """Per-tick degradation bookkeeping for the paged path: deadline
        eviction, shed collection, and load-shed mode when page-pool
        pressure stays critical for ``shed_patience`` consecutive ticks."""
        cfg = self.cfg
        for req in self.sched.expire_deadlines(self.kv, self.ticks):
            self.results[req.rid] = req.output
            self.outcomes[req.rid] = "timeout"
            self.obs.counter("serve_requests", outcome="timeout")
            self.obs.instant("timeout", rid=req.rid)
        if cfg.shed_patience > 0:
            st = self.kv.stats()
            frac = st["pages_used"] / max(1, st["pages_total"] - 1)
            if frac >= cfg.shed_pressure:
                self._pressure_ticks += 1
            else:
                self._pressure_ticks = 0
            if self._pressure_ticks >= cfg.shed_patience:
                self._shed_mode_ticks += 1
                self.sched.shed_waiting(
                    below_priority=cfg.shed_min_priority)

    def _step_paged(self) -> None:
        """One continuous-batching tick: admit (consulting the prefix
        cache), execute pending COW copies, grow decode pages, then run
        jitted ``paged_step`` over the tick's active rows grouped by
        padded length — wide prefill chunks in one call, decode rows
        (and single-token cache-hit suffix prefills) in a ``T == 1``
        call that keeps the Pallas decode path and never pays the
        chunk padding.  Requests join and leave the batch per tick;
        there are no phase epochs."""
        cfg = self.cfg
        if not self.sched.has_work:
            return
        self.ticks += 1
        obs = self.obs
        self._degrade_tick()
        with obs.span("admission", tick=self.ticks):
            prefix = self.prefix
            if obs.enabled and prefix is not None:
                prefix = _TracedPrefix(prefix, obs)
            admitted = self.sched.admit(self.kv, now=self.ticks,
                                        prefix=prefix)
            for req in admitted:
                if obs.enabled:
                    obs.instant("admit", rid=req.rid,
                                prompt=int(len(req.prompt)),
                                matched=int(req.matched_tokens))
                if req.cow is not None:
                    with obs.span("cow", rid=req.rid):
                        self._exec_cow(req)
        shed = self.sched.drain_shed()
        for req in shed:
            self.results[req.rid] = req.output
            self.outcomes[req.rid] = "shed"
            obs.counter("serve_requests", outcome="shed")
            obs.instant("shed", rid=req.rid)

        # decode rows claim their next page BEFORE the batch is built —
        # under page pressure this may evict actives (prefill included),
        # so jobs are selected afterwards
        with obs.span("reclaim", tick=self.ticks):
            preempted = self.sched.ensure_decode_pages(self.kv)
        for req in preempted or ():
            obs.counter("serve_preemptions")
            obs.instant("preempt", rid=req.rid,
                        preemptions=req.preemptions)
        jobs = self.sched.prefill_jobs()
        decoding = self.sched.decoding()
        if not jobs and not decoding:
            # stall valve: work is queued but nothing ran this tick
            self._stall_ticks = 0 if (admitted or shed) else \
                self._stall_ticks + 1
            if self._stall_ticks > self.STALL_LIMIT:
                raise RuntimeError("paged scheduler made no progress")
            return
        self._stall_ticks = 0

        # group rows by padded length: wide chunks would drag decode rows
        # through a T-padded trace (the T > 1 path attends with the XLA
        # fallback over the whole page view), so decode only shares a
        # call with prefills that are themselves single-token
        chunk_t = _pow2_at_least(max((j.count for j in jobs), default=1))
        if chunk_t == 1:
            groups = [(jobs, decoding)]
        else:
            groups = [(jobs, []), ([], decoding)]
        for g_jobs, g_decode in groups:
            if g_jobs or g_decode:
                self._exec_rows(g_jobs, g_decode)

    def _exec_rows(self, jobs, decoding) -> None:
        """Build one padded (B, T) batch from the given prefill jobs +
        decode rows, run it through ``paged_step``, and harvest: advance
        lengths, sample next tokens, finish completed requests.

        Traced as one span named by the call's width (``decode`` for
        T == 1, single-token prefills included, else ``prefill``) over
        four host phases: ``rows.build`` (the batch and its page view),
        ``rows.launch`` (the dispatch, which returns before the device
        finishes), ``rows.wait`` (the host blocked on the device for the
        picked tokens) and ``rows.commit`` (KV lengths, traffic, tokens,
        finished requests).  A decode span also records how much of the
        attention's page view is live: ``view_pages``, ``live_keys`` (the
        keys the rows attend) and ``view_keys`` (slots x view)."""
        obs = self.obs
        B = self.cfg.batch
        T = _pow2_at_least(max([j.count for j in jobs], default=1))
        with obs.span("decode" if T == 1 else "prefill", tick=self.ticks,
                      prefill_rows=len(jobs),
                      decode_rows=len(decoding)) as group:
            with obs.span("rows.build"):
                tokens = np.zeros((B, T), np.int32)
                counts = np.zeros((B,), np.int32)
                for j in jobs:
                    tokens[j.req.slot, :j.count] = \
                        j.req.prompt[j.start:j.start + j.count]
                    counts[j.req.slot] = j.count
                for r in decoding:
                    tokens[r.slot, 0] = r.generated[-1]
                    counts[r.slot] = 1
                mp = self._pages_view(max(
                    int(self.kv.lengths[s]) + int(counts[s])
                    for s in range(B) if counts[s] > 0))
                if group is not None and T == 1:
                    live = counts > 0
                    group.args.update(
                        view_pages=mp,
                        live_keys=int((self.kv.lengths[live]
                                       + counts[live]).sum()),
                        view_keys=B * mp * self.kv.cfg.page_size)
            with obs.span("rows.launch"):
                rows_dev, picked_dev = self._exec_step(tokens, counts, mp)
            with obs.span("rows.wait"):
                picked = np.asarray(picked_dev) if self._greedy \
                    else np.asarray(rows_dev)
            with obs.span("rows.commit"):
                self._commit_rows(jobs, decoding, counts, picked)

    def _commit_rows(self, jobs, decoding, counts, picked) -> None:
        """Harvest one executed batch: advance KV lengths, tally traffic,
        append each row's next token and finish completed requests."""
        cfg = self.cfg
        by_slot = {j.req.slot: j for j in jobs}
        tr, page = self._traffic, self.kv.cfg.page_size
        for slot in range(cfg.batch):
            if counts[slot] == 0:
                continue
            job = by_slot.get(slot)
            if job is not None:                      # prefill chunk
                req = job.req
                self.kv.advance(slot, job.count)
                ctx = int(self.kv.lengths[slot])     # attended context
                tr["gb_read_tokens"] += ctx
                tr["dram_read_tokens"] += self.kv.pages_for(ctx) * page
                tr["written_tokens"] += job.count
                tr["prefill_tokens"] += job.count
                self.sched.finish_prefill_chunk(req, job.count)
                if req.phase is not Phase.DECODE:
                    continue                         # more chunks to go
            else:                                    # decode row
                req = next(r for r in decoding if r.slot == slot)
                self.kv.advance(slot, 1)
                ctx = int(self.kv.lengths[slot])
                tr["gb_read_tokens"] += ctx
                tr["dram_read_tokens"] += self.kv.pages_for(ctx) * page
                tr["written_tokens"] += 1
                tr["decode_rows"] += 1
                tr["decode_keys"] += ctx
            tok = int(picked[slot]) if self._greedy \
                else self._pick(picked[slot])
            req.generated.append(tok)
            if req.n_generated >= req.max_new_tokens or tok == cfg.eos_id:
                self._finish(req)

    # ------------------------------------------------------------------
    # fleet surface (serving.fleet): cancel, in-flight audit, migration
    # ------------------------------------------------------------------

    def inflight(self) -> list[int]:
        """rids submitted but not yet reaped into ``results`` — on host
        loss the router re-admits exactly these on the survivors."""
        if self.cfg.kv_mode == "dense":
            live = [item[0] for item in self.queue]
            live += [s.request_id for s in self.slots
                     if s.request_id is not None]
            return [rid for rid in live if rid not in self.results]
        return [rid for rid in self._requests if rid not in self.results]

    def cancel(self, rid: int) -> bool:
        """Withdraw one unfinished request, releasing its pages (shared
        prefix pages only decref).  The fleet retires the losing twin of
        a hedged dispatch this way.  Returns True when something was
        actually cancelled."""
        if rid in self.results:
            return False
        if self.cfg.kv_mode == "dense":
            for i, item in enumerate(self.queue):
                if item[0] == rid:
                    del self.queue[i]
                    self.results[rid] = []
                    self.outcomes[rid] = "cancelled"
                    return True
            for i, s in enumerate(self.slots):
                if s.request_id == rid:
                    self.results[rid] = list(s.generated)
                    self.outcomes[rid] = "cancelled"
                    self.slots[i] = _Slot()
                    return True
            return False
        req = self._requests.get(rid)
        if req is None or self.sched.cancel(self.kv, rid) is None:
            return False
        self.results[rid] = req.output
        self.outcomes[rid] = "cancelled"
        self.obs.counter("serve_requests", outcome="cancelled")
        return True

    def export_prefix_pages(self, tokens, n_tokens: int):
        """Migration SOURCE: the KV payloads of the full-page cached
        prefix of ``tokens[:n_tokens]``, as (segment tokens, {pool entry:
        np.ndarray}) pairs in path order.  Stops at the first uncached or
        partial page — callers migrate what exists and recompute the
        rest."""
        if getattr(self, "prefix", None) is None:
            return []
        out = []
        for node in self.prefix.path_nodes(tokens, n_tokens):
            vals = {k: np.asarray(v[:, node.page])
                    for k, v in self.pool.items()}
            out.append((node.tokens, vals))
        return out

    def import_prefix_pages(self, segments) -> int:
        """Migration TARGET: graft exported page payloads into this
        host's pool + trie so the next lookup serves them locally —
        the page is TRANSFERRED, never re-prefilled.  Segments already
        cached here are skipped; a dry pool ends the import early
        (partial import is fine, the remainder is recomputed).  Returns
        the prefix tokens now cached locally."""
        if getattr(self, "prefix", None) is None:
            return 0
        node, matched = self.prefix.root, 0
        ps = self.kv.cfg.page_size
        for seg, vals in segments:
            seg = tuple(int(t) for t in seg)
            if len(seg) != ps:
                break                        # only full pages migrate
            child = node.children.get(seg)
            if child is not None and child.n_tokens == ps:
                node, matched = child, matched + ps
                continue
            try:
                page = self.kv.adopt_page()
            except MemoryError:
                break
            ctx = self._mesh_ctx()
            try:
                if ctx is not None:
                    ctx.__enter__()
                self.pool = _write_pool_page(
                    self.pool, jnp.asarray(page, jnp.int32),
                    {k: jnp.asarray(v) for k, v in vals.items()})
            finally:
                if ctx is not None:
                    ctx.__exit__(None, None, None)
            node = self.prefix.adopt_segment(node, seg, page)
            matched += ps
        return matched

    def drop_prefix_path(self, tokens, n_tokens: int) -> int:
        """Migration SOURCE, after a successful transfer: drop the local
        trie path for the migrated prefix (ownership moved — pages are
        owned once).  Pages still feeding live slots survive."""
        if getattr(self, "prefix", None) is None:
            return 0
        return self.prefix.drop_path(tokens, n_tokens)

    # ------------------------------------------------------------------
    # accounting
    # ------------------------------------------------------------------

    def degradation_stats(self) -> dict:
        """Outcome counters + load-shed bookkeeping (all modes)."""
        counts = {"ok": 0, "timeout": 0, "shed": 0}
        for v in self.outcomes.values():
            counts[v] = counts.get(v, 0) + 1
        counts["shed_mode_ticks"] = self._shed_mode_ticks
        return counts

    def prefix_stats(self) -> dict:
        """Radix-cache counters (hit rate, matched tokens/pages, COW and
        eviction counts); empty when the cache is off or the mode dense."""
        if getattr(self, "prefix", None) is None:
            return {}
        st = self.prefix.stats()
        st["cow_copies"] = self.cow_copies
        return st

    def check_kv(self) -> None:
        """Full pool + trie invariant audit (tests): every page's refcount
        must equal its slot mappings plus trie references."""
        if getattr(self, "prefix", None) is not None:
            self.prefix.check_invariants()
        else:
            self.kv.check_invariants()

    def traffic_stats(self) -> dict:
        """Observed KV traffic (tokens + bytes) at the paper's two fetch
        levels: ``gb_*`` is token-exact attended context (global-buffer
        level), ``dram_*`` is page-granular pool reads.  Split by kind of
        row: ``prefill_tokens`` (prompt positions computed in prefill
        chunks), ``decode_rows`` (rows fed a generated token) and
        ``decode_keys`` (the keys those rows attended).  Paged modes
        only; dense reports zeros (its cache is a flat reservation)."""
        tr = dict(self._traffic)
        if self.cfg.kv_mode != "dense":
            bpt = self.kv.cfg.page_bytes / self.kv.cfg.page_size
        else:
            bpt = 0.0
        tr["gb_read_bytes"] = tr["gb_read_tokens"] * bpt
        tr["dram_read_bytes"] = tr["dram_read_tokens"] * bpt
        tr["written_bytes"] = tr["written_tokens"] * bpt
        return tr

    def telemetry(self) -> dict:
        """One structured snapshot of everything the engine knows —
        request outcomes, KV-pool utilization, prefix-cache hit rate,
        observed traffic — mirrored into the metrics registry as
        ``serve.*`` gauges (the pull half of the obs design) and returned
        as a plain dict (the ``/stats`` surface)."""
        snap = {
            "mode": self.cfg.kv_mode,
            "ticks": getattr(self, "ticks", None) if
            self.cfg.kv_mode != "dense" else self._dense_tick,
            "outcomes": self.degradation_stats(),
            "kv": self.kv_stats(),
            "prefix": self.prefix_stats(),
            "traffic": self.traffic_stats(),
        }
        m = self.obs.metrics
        m.absorb(snap["outcomes"], prefix="serve.outcomes.")
        m.absorb(snap["kv"], prefix="serve.kv.")
        m.absorb(snap["prefix"], prefix="serve.prefix.")
        m.absorb(snap["traffic"], prefix="serve.traffic.")
        return snap

    def kv_stats(self) -> dict:
        """Resident-KV accounting (benchmarks): paged modes report pool
        counters; dense reports the up-front reservation."""
        if self.cfg.kv_mode != "dense":
            return self.kv.stats()
        leaves = jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: self.bundle.init_cache(
                self.cfg.batch, self.cfg.max_len)))
        total = int(sum(np.prod(l.shape) * l.dtype.itemsize for l in leaves))
        return {"bytes_resident": total, "peak_bytes": total,
                "pages_total": 0, "pages_used": 0, "utilization": 1.0,
                "fragmentation": 0.0, "evictions": 0}
