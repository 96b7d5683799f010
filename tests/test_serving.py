"""Paged KV serving subsystem: block pool, paged kernel, scheduler, engine.

Covers the acceptance checklist of the paged-serving PR: paged-vs-dense
decode equivalence, block-pool alloc/free/evict invariants (hypothesis),
preemption of low-priority work by a high-priority late arrival under page
pressure, the paged flash-decode kernel against its pure-JAX oracle, and
the slot-write layout regression (cache entries whose batch axis is NOT
axis 1)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import (BlockPoolKV, PagedKVConfig, Phase, PhaseScheduler,
                           RadixPrefixCache, Request, SchedulerConfig,
                           ServeConfig, ServingEngine)


# ---------------------------------------------------------------------------
# paged flash-decode kernel vs oracle
# ---------------------------------------------------------------------------

def _pool_setup(seed=0, B=3, H=8, Hkv=2, Dh=32, P=12, pg=16, MP=4):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(P, pg, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, pg, Hkv, Dh)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]], jnp.int32)
    lens = jnp.asarray([40, 17, 64], jnp.int32)
    return q, k, v, pt, lens


# (Hkv, G, Dh) of the kernel cases; page 16, blocks of 2 pages (32 tokens)
# over a 5-page view, so the view is not a multiple of the block
KERNEL_SHAPES = [(2, 4, 32), (1, 5, 64), (8, 4, 128), (4, 1, 128)]
KERNEL_PPB, KERNEL_MP = 2, 5
# length 1, on a block edge, one past it, the whole view
KERNEL_LENS = (1, 32, 33, 80)
GARBAGE = (17, 18)      # mapped by no slot; the dead table points at them


def _kernel_case(Hkv, G, Dh, seed=0, pg=16):
    """q, pools, table, lengths and int8 pools with their scales.  Each
    slot's live pages are its own; its table past the length points at
    trash page 0 and at the unmapped GARBAGE pages."""
    rng = np.random.default_rng(seed)
    B, P = len(KERNEL_LENS), 20
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(P, pg, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, pg, Hkv, Dh)), jnp.float32)
    table = np.zeros((B, KERNEL_MP), np.int32)
    free = iter(range(1, min(GARBAGE)))
    for b, n in enumerate(KERNEL_LENS):
        live = -(-n // pg)
        table[b, :live] = [next(free) for _ in range(live)]
        table[b, live:] = ([0] + list(GARBAGE) * KERNEL_MP)[:KERNEL_MP - live]
    int8 = tuple(
        jnp.asarray(rng.integers(-127, 127, (P, pg, Hkv, Dh)), jnp.int8)
        for _ in range(2)) + tuple(
        jnp.asarray(rng.uniform(0.01, 0.02, (P, pg, Hkv)), jnp.float32)
        for _ in range(2))
    return q, k, v, jnp.asarray(table), jnp.asarray(KERNEL_LENS), int8


def _kernel(ppb, *args, layer=None):
    """The kernel with ``ppb`` pages a block, or through the jitted
    wrapper (pages per block from the shapes) when ``ppb`` is None.
    Pools of all layers are read at ``layer``; one layer's pools (layer
    None) are that layer of a one-layer stack."""
    from repro.kernels import ops
    from repro.kernels import paged_attention as kpaged
    if ppb is None:
        return ops.paged_flash_decode(*args, layer=layer)
    if layer is None:
        q, *pools = args
        args = [q] + [x[None] if i in (0, 1, 4, 5) else x
                      for i, x in enumerate(pools)]
        layer = 0
    q, k, v, pt, lens, *scales = args
    return kpaged.paged_flash_decode_pallas(
        q, k, v, pt, lens, jnp.int32(layer), *scales, pages_per_block=ppb,
        interpret=True)


_KERNEL_CASES = ([pytest.param(None, None, id="pool_setup")]
                 + [pytest.param(s, ppb, id=f"{s}-ppb{ppb or 'auto'}")
                    for s in KERNEL_SHAPES for ppb in (KERNEL_PPB, None)])
# the case's pools alone, or as the first or last of LAYERS stacked layers
LAYERS = 3
_LAYER_CASES = [pytest.param(None, id="one-layer"),
                pytest.param(0, id="l0"),
                pytest.param(LAYERS - 1, id="l-last")]


def _in_layer(args, layer):
    """``args`` with its pools (and scales) as layer ``layer`` of LAYERS
    stacked layers whose others hold noise, so a read of the wrong layer
    shows; ``layer`` None leaves them one layer's."""
    if layer is None:
        return args
    rng = np.random.default_rng(7)

    def stack(x):
        noise = [jnp.asarray(rng.uniform(-100, 100, x.shape)).astype(x.dtype)
                 for _ in range(LAYERS)]
        noise[layer] = x
        return jnp.stack(noise)

    return tuple(stack(x) if i in (1, 2, 5, 6) else x
                 for i, x in enumerate(args))


def _case_args(shape, int8=False):
    if shape is None:
        q, k, v, pt, lens = _pool_setup()
        if not int8:
            return q, k, v, pt, lens
        rng = np.random.default_rng(1)
        P, pg, Hkv, Dh = 12, 16, 2, 32
        kq = jnp.asarray(rng.integers(-127, 127, (P, pg, Hkv, Dh)), jnp.int8)
        vq = jnp.asarray(rng.integers(-127, 127, (P, pg, Hkv, Dh)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.02, (P, pg, Hkv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.02, (P, pg, Hkv)), jnp.float32)
        return q, kq, vq, pt, lens, ks, vs
    q, k, v, pt, lens, (kq, vq, ks, vs) = _kernel_case(*shape)
    return (q, kq, vq, pt, lens, ks, vs) if int8 else (q, k, v, pt, lens)


@pytest.mark.parametrize("layer", _LAYER_CASES)
@pytest.mark.parametrize("shape,ppb", _KERNEL_CASES)
def test_paged_kernel_matches_ref(shape, ppb, layer):
    from repro.kernels.ref import paged_decode_ref
    args = _case_args(shape)
    out = _kernel(ppb, *_in_layer(args, layer), layer=layer)
    ref = paged_decode_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("layer", _LAYER_CASES)
@pytest.mark.parametrize("shape,ppb", _KERNEL_CASES)
def test_paged_kernel_int8_matches_ref(shape, ppb, layer):
    from repro.kernels.ref import paged_decode_ref
    args = _case_args(shape, int8=True)
    out = _kernel(ppb, *_in_layer(args, layer), layer=layer)
    ref = paged_decode_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("shape,ppb", _KERNEL_CASES)
def test_paged_attention_trash_page_isolated(shape, ppb):
    """Pages beyond a slot's length (trash page 0, and garbage pages the
    dead table points at or no table maps) never leak into the output:
    changing them leaves results bitwise identical."""
    q, k, v, pt, lens = _case_args(shape)
    out1 = _kernel(ppb, q, k, v, pt, lens)
    k2, v2 = k.at[0].mul(2.0), v.at[0].mul(-3.0)
    for g in ((10, 11) if shape is None else GARBAGE):
        k2, v2 = k2.at[g].add(7.0), v2.at[g].add(1.0)
    out2 = _kernel(ppb, q, k2, v2, pt, lens)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# block pool
# ---------------------------------------------------------------------------

def _kvcfg(**kw):
    base = dict(num_slots=4, max_len=64, page_size=8, num_pages=17)
    base.update(kw)
    return PagedKVConfig(**base)


def test_block_pool_basics():
    kv = BlockPoolKV(_kvcfg())
    assert kv.free_pages == 16
    kv.ensure(0, 20)                       # 3 pages
    kv.advance(0, 20)
    assert kv.used_pages == 3 and kv.capacity(0) == 24
    st = kv.stats()
    assert st["tokens_resident"] == 20
    assert st["bytes_resident"] == 3 * kv.cfg.page_bytes
    assert 0.0 < st["fragmentation"] < 1.0
    kv.check_invariants()
    kv.free_slot(0)
    assert kv.free_pages == 16 and kv.capacity(0) == 0
    kv.check_invariants()


def test_block_pool_dry_raises():
    kv = BlockPoolKV(_kvcfg(num_pages=4))   # 3 usable
    kv.ensure(0, 24)
    with pytest.raises(MemoryError):
        kv.ensure(1, 8)
    kv.check_invariants()


def test_block_pool_property_random_ops():
    pytest.importorskip("hypothesis")  # optional (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st

    ops_strategy = st.lists(
        st.tuples(st.sampled_from(["ensure", "advance", "free"]),
                  st.integers(0, 3), st.integers(1, 64)),
        min_size=1, max_size=60)

    @given(ops=ops_strategy)
    @settings(max_examples=80, deadline=None)
    def run(ops):
        kv = BlockPoolKV(_kvcfg())
        for op, slot, n in ops:
            if op == "ensure":
                try:
                    kv.ensure(slot, n)
                except MemoryError:
                    pass
            elif op == "advance":
                room = kv.capacity(slot) - int(kv.lengths[slot])
                if room > 0:
                    kv.advance(slot, min(n, room))
            else:
                kv.free_slot(slot)
            # the PR's property: alloc/free/evict never double-assigns a
            # page, never allocates trash, never leaks
            kv.check_invariants()

    run()


# ---------------------------------------------------------------------------
# scheduler: phases + preemption
# ---------------------------------------------------------------------------

def _req(rid, n_prompt, prio, max_new=8):
    return Request(rid=rid, prompt=np.zeros(n_prompt, np.int32),
                   priority=prio, arrival=rid, max_new_tokens=max_new)


def test_scheduler_high_priority_late_arrival_preempts():
    """Two low-priority requests hold the whole pool in DECODE; a
    high-priority arrival evicts the lowest/latest one and is admitted."""
    kv = BlockPoolKV(_kvcfg(num_slots=2, num_pages=7))   # 6 usable pages
    sched = PhaseScheduler(SchedulerConfig(num_slots=2))
    lo0, lo1 = _req(0, 16, prio=0), _req(1, 16, prio=0)
    sched.submit(lo0)
    sched.submit(lo1)
    assert len(sched.admit(kv)) == 2                     # 3 pages each
    for r in (lo0, lo1):
        kv.advance(r.slot, 16)
        r.prefill_pos = 16
        r.phase = Phase.DECODE
        r.generated = [7]
    assert kv.free_pages == 0

    hi = _req(2, 16, prio=5)
    sched.submit(hi)
    admitted = sched.admit(kv)
    assert admitted == [hi] and hi.phase is Phase.PREFILL
    # the LATEST low-priority arrival was evicted back to waiting with its
    # generated token folded into the prompt for recompute
    assert lo1.phase is Phase.WAITING and lo1.preemptions == 1
    assert lo1.history == [7] and len(lo1.prompt) == 17
    assert lo0.phase is Phase.DECODE                    # survivor
    assert kv.stats()["evictions"] == 1
    kv.check_invariants()


def test_scheduler_no_preemption_of_equal_or_higher_priority():
    kv = BlockPoolKV(_kvcfg(num_slots=2, num_pages=7))
    sched = PhaseScheduler(SchedulerConfig(num_slots=2))
    a, b = _req(0, 16, prio=3), _req(1, 16, prio=3)
    sched.submit(a)
    sched.submit(b)
    sched.admit(kv)
    c = _req(2, 16, prio=3)                             # equal priority
    sched.submit(c)
    assert sched.admit(kv) == []                        # must wait
    assert a.preemptions == b.preemptions == 0


def test_decode_page_pressure_self_evicts_not_equal_peer():
    """When a decoding slot needs its next page and only EQUAL-priority
    peers are active, it evicts itself — peers are never targeted."""
    kv = BlockPoolKV(_kvcfg(num_slots=2, num_pages=5))   # 4 usable pages
    sched = PhaseScheduler(SchedulerConfig(num_slots=2,
                                           decode_headroom_pages=0))
    a, b = _req(0, 16, prio=2), _req(1, 16, prio=2)
    sched.submit(a)
    sched.submit(b)
    sched.admit(kv)                                      # 2 pages each
    for r in (a, b):
        kv.advance(r.slot, 16)
        r.prefill_pos = 16
        r.phase = Phase.DECODE
        r.generated = [1]
    assert kv.free_pages == 0
    evicted = sched.ensure_decode_pages(kv)              # a needs page 3
    assert a in evicted and a.phase is Phase.WAITING
    assert b.phase is Phase.DECODE and b.preemptions == 0
    kv.check_invariants()


def test_scheduler_prefill_budget_chunks():
    kv = BlockPoolKV(_kvcfg(num_slots=4, num_pages=33, max_len=128))
    cfg = SchedulerConfig(num_slots=4, prefill_chunk=16,
                          prefill_token_budget=24)
    sched = PhaseScheduler(cfg)
    long_req, short_req = _req(0, 40, 0), _req(1, 8, 0)
    sched.submit(long_req)
    sched.submit(short_req)
    sched.admit(kv)
    jobs = sched.prefill_jobs()
    # one chunk per request per tick, budget-capped: 16 (long) + 8 (short)
    assert [(j.req.rid, j.count) for j in jobs] == [(0, 16), (1, 8)]
    for j in jobs:
        sched.finish_prefill_chunk(j.req, j.count)
    assert short_req.phase is Phase.DECODE
    assert long_req.phase is Phase.PREFILL and long_req.prefill_pos == 16


# ---------------------------------------------------------------------------
# engine: dense/paged equivalence + slot-write layout
# ---------------------------------------------------------------------------

def _serve(arch, kv_mode, prompts, **kw):
    from repro.launch.serve import build_engine
    engine, vocab = build_engine(arch, slots=2, max_len=48, max_new=6,
                                 kv_mode=kv_mode, page_size=8, **kw)
    for p in prompts:
        engine.submit(p)
    return engine.run(), engine


def _prompts(vocab=256, n=5, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(n_)).astype(np.int32)
            for n_ in rng.integers(4, 20, n)]


def test_paged_vs_dense_equivalence():
    """Same prompts, same seeds -> identical greedy tokens from the dense
    slot engine and the paged block-pool engine (and its int8 variant must
    produce full-length outputs too)."""
    prompts = _prompts()
    dense, _ = _serve("qwen3-4b", "dense", prompts)
    paged, eng = _serve("qwen3-4b", "paged", prompts)
    assert dense == paged
    assert eng.kv_stats()["peak_bytes"] > 0
    int8, _ = _serve("qwen3-4b", "paged_int8", prompts)
    assert sorted(int8) == sorted(dense)
    assert all(len(v) == 6 for v in int8.values())


@pytest.mark.parametrize("kv_mode", ["paged", "paged_int8"])
def test_paged_engine_pallas_matches_xla(kv_mode, monkeypatch):
    """Greedy tokens are identical with the decode kernel forced on
    (interpreted) and with the XLA gather path, over ticks that mix
    prefill chunks and decode rows.  Blocks are cut to 2 pages (16
    tokens), so the slots' lengths cross block edges as they grow."""
    import dataclasses as dc

    from repro.kernels import paged_attention as kpaged
    from repro.launch import serve as launch_serve
    from repro.obs import REGISTRY
    real_bundle = launch_serve.get_bundle
    page_bytes = 8 * 2 * 16 * (1 if kv_mode == "paged_int8" else 4)
    monkeypatch.setattr(kpaged, "BLOCK_BYTES", 2 * page_bytes)
    jax.clear_caches()

    def serve(impl):
        monkeypatch.setattr(
            launch_serve, "get_bundle", lambda arch, smoke=False: dc.replace(
                real_bundle(arch, smoke), cfg=dc.replace(
                    real_bundle(arch, smoke).cfg, attn_impl=impl)))
        return _serve("qwen3-4b", kv_mode, _prompts(n=4, seed=5),
                      prefill_chunk=8)[0]

    before = REGISTRY.get_counter("kernel_dispatch",
                                  kernel="paged_flash_decode",
                                  impl="int8" if kv_mode == "paged_int8"
                                  else "pallas")
    xla, pallas = serve("xla"), serve("pallas")
    jax.clear_caches()
    assert REGISTRY.get_counter(
        "kernel_dispatch", kernel="paged_flash_decode",
        impl="int8" if kv_mode == "paged_int8" else "pallas") > before
    assert pallas == xla
    assert all(len(v) == 6 for v in pallas.values())


def test_engine_preemption_under_page_pressure():
    """Decode growth past the admission reservation triggers eviction of
    the lowest-priority request; everyone still completes."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine("qwen3-4b", slots=3, max_len=64,
                                 max_new=16, kv_mode="paged", page_size=8,
                                 num_pages=11)
    rng = np.random.default_rng(1)
    for prio in (0, 0, 5):
        engine.submit(rng.integers(0, vocab, 12).astype(np.int32),
                      priority=prio)
    res = engine.run()
    assert len(res) == 3 and all(len(v) == 16 for v in res.values())
    assert engine.kv_stats()["evictions"] >= 1
    assert engine._requests[2].preemptions == 0   # high priority survives


def test_write_slot_uses_declared_batch_axes():
    """Regression for the seed's hardwired (L, B, ...) slot-write layout:
    a cache entry with batch at axis 2 (recurrentgemma's grouped states)
    round-trips correctly when the bundle declares its axes."""

    class DeclaredBundle:
        def cache_batch_axes(self, cache):
            return {"weird": 2, "k": 1, "length": 0}

    eng = ServingEngine.__new__(ServingEngine)     # no model needed
    eng.bundle = DeclaredBundle()
    eng._cache_axes = None
    cache = {
        "weird": jnp.zeros((2, 3, 4, 5)),          # batch axis 2 (size 4)
        "k": jnp.zeros((2, 4, 6)),                 # batch axis 1
        "length": jnp.zeros((4,), jnp.int32),
    }
    one = {
        "weird": jnp.ones((2, 3, 1, 5)) * 7,
        "k": jnp.ones((2, 1, 6)) * 3,
        "length": jnp.asarray([9], jnp.int32),
    }
    out = eng._write_slot(cache, one, 2)
    np.testing.assert_array_equal(np.asarray(out["weird"][:, :, 2]), 7.0)
    np.testing.assert_array_equal(np.asarray(out["weird"][:, :, 1]), 0.0)
    np.testing.assert_array_equal(np.asarray(out["k"][:, 2]), 3.0)
    np.testing.assert_array_equal(np.asarray(out["k"][:, 0]), 0.0)
    assert int(out["length"][2]) == 9 and int(out["length"][0]) == 0


def test_serving_recurrentgemma_grouped_states():
    """The family whose cache layout violates the old axis-1 assumption
    now serves through the pooled engine (declared CACHE_BATCH_AXES)."""
    from repro.launch.serve import run as serve_run
    results = serve_run("recurrentgemma-9b", smoke=True, n_requests=3,
                        slots=2, prompt_len=6, max_new=4, max_len=32)
    assert len(results) == 3
    assert all(len(v) == 4 for v in results.values())


def test_dense_prefill_bucketing_trace_reuse():
    """Length-bucketed prefill: distinct prompt lengths within one bucket
    share a single jit trace (the seed retraced per length)."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine("qwen3-4b", slots=2, max_len=64, max_new=2)
    rng = np.random.default_rng(0)
    for n in (5, 6, 7, 8):                  # one bucket (8)
        engine.submit(rng.integers(0, vocab, n).astype(np.int32))
    engine.run()
    n_traces = engine._prefill._cache_size()
    assert n_traces == 1, n_traces


def test_paged_pool_specs_shapes():
    from repro.parallel.sharding import paged_pool_specs
    from repro.runtime import compat
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    specs = paged_pool_specs(mesh, kv_heads=4, head_dim=64)
    assert set(specs) >= {"k", "v", "k_scale", "v_scale", "page_table",
                          "lengths"}
    assert len(specs["k"]) == 5 and len(specs["k_scale"]) == 4


# ---------------------------------------------------------------------------
# graceful degradation: deadlines, admission retry/shed, load-shed mode
# ---------------------------------------------------------------------------

def test_paged_deadline_evicts_but_engine_keeps_serving():
    """A request whose deadline passes mid-decode is evicted with its
    partial output (outcome "timeout") while the other request runs to
    completion — one stuck request cannot hold pages forever."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine("qwen3-4b", slots=2, max_len=48,
                                 max_new=10, kv_mode="paged", page_size=8)
    rng = np.random.default_rng(0)
    doomed = engine.submit(rng.integers(0, vocab, 8).astype(np.int32),
                           deadline=4)
    healthy = engine.submit(rng.integers(0, vocab, 8).astype(np.int32))
    res = engine.run()
    assert engine.outcomes == {doomed: "timeout", healthy: "ok"}
    assert 0 < len(res[doomed]) < 10               # partial output kept
    assert len(res[healthy]) == 10
    engine.kv.check_invariants()                   # pages were returned
    stats = engine.degradation_stats()
    assert stats["timeout"] == 1 and stats["ok"] == 1


def test_admission_backoff_terminates_without_deadlock():
    """With retry/backoff configured, a request that cannot fit yet stops
    blocking the queue head, retries with exponential hold-off, and still
    completes once capacity frees — no shed, no deadlock."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine("qwen3-4b", slots=2, max_len=48,
                                 max_new=6, kv_mode="paged", page_size=8,
                                 num_pages=7, max_admission_retries=0,
                                 admission_backoff=1)
    rng = np.random.default_rng(0)
    for prio in (5, 5, 0):                         # third can't fit at first
        engine.submit(rng.integers(0, vocab, 16).astype(np.int32),
                      priority=prio)
    res = engine.run()
    assert len(res) == 3 and all(len(v) == 6 for v in res.values())
    assert set(engine.outcomes.values()) == {"ok"}


def test_admission_retry_budget_sheds():
    """When the retry budget blows before capacity frees, the request is
    SHED (outcome "shed", empty output) instead of waiting forever; the
    admitted work is unaffected."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine("qwen3-4b", slots=2, max_len=64,
                                 max_new=24, kv_mode="paged", page_size=8,
                                 num_pages=11, max_admission_retries=2,
                                 admission_backoff=1)
    rng = np.random.default_rng(0)
    a = engine.submit(rng.integers(0, vocab, 16).astype(np.int32), priority=5)
    b = engine.submit(rng.integers(0, vocab, 16).astype(np.int32), priority=5)
    c = engine.submit(rng.integers(0, vocab, 40).astype(np.int32), priority=0)
    res = engine.run()
    assert engine.outcomes[c] == "shed" and res[c] == []
    assert engine.outcomes[a] == engine.outcomes[b] == "ok"
    assert len(res[a]) == 24 and len(res[b]) == 24


def test_load_shed_mode_under_sustained_pool_pressure():
    """When the page pool stays critical for `shed_patience` consecutive
    ticks, waiting sub-priority work is dropped wholesale; requests
    already holding pages keep running."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine("qwen3-4b", slots=2, max_len=64,
                                 max_new=24, kv_mode="paged", page_size=8,
                                 num_pages=7, shed_pressure=0.9,
                                 shed_patience=2, shed_min_priority=1)
    rng = np.random.default_rng(0)
    a = engine.submit(rng.integers(0, vocab, 16).astype(np.int32), priority=5)
    b = engine.submit(rng.integers(0, vocab, 16).astype(np.int32), priority=5)
    c = engine.submit(rng.integers(0, vocab, 16).astype(np.int32), priority=0)
    res = engine.run()
    assert engine.outcomes[c] == "shed"
    assert engine.degradation_stats()["shed_mode_ticks"] >= 1
    assert len(res[a]) == 24 and len(res[b]) == 24


def test_seeded_burst_composes_backoff_shed_and_preemption():
    """One seeded burst must light up every pressure valve AT ONCE — the
    degradation paths are only trustworthy composed, not just in the
    isolated single-mechanism tests above: admission backoff (a retried
    request eventually admits and completes), preemption by page pressure
    (a high-priority late arrival evicts a low-priority victim), and
    load-shed mode (sub-priority waiting work dropped wholesale) — with
    every request reaching exactly one outcome and pool + trie invariants
    intact."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine(
        "qwen3-4b", slots=3, max_len=64, max_new=8, kv_mode="paged",
        page_size=8, num_pages=11, max_admission_retries=6,
        admission_backoff=1, shed_pressure=0.85, shed_patience=4,
        shed_min_priority=1)
    rng = np.random.default_rng(9)

    def sub(n_tokens, priority):
        return engine.submit(rng.integers(0, vocab, n_tokens)
                             .astype(np.int32), priority=priority)

    # t=0 burst: three low-priority requests fill the slots and 9 of the
    # 10 usable pages (2 prompt pages + 1 headroom each)
    victims = [sub(12, 0), sub(12, 0), sub(12, 0)]
    for _ in range(2):
        engine.step()
    # late arrivals against a hot pool: the VIPs preempt every victim,
    # the mid-priority request finds only VIPs active (nothing evictable
    # below it) and must back off, the sub-priority pair is shed bait
    vips = [sub(12, 5), sub(12, 5), sub(12, 5)]
    backoff = sub(12, 2)
    doomed = [sub(16, 0), sub(16, 0)]
    res = engine.run()

    stats = engine.degradation_stats()
    counts = {k: stats[k] for k in ("ok", "timeout", "shed")}
    assert sum(counts.values()) == 9       # every rid reached one outcome
    # high priority never preempted, full output
    for vip in vips:
        assert engine.outcomes[vip] == "ok" and len(res[vip]) == 8
        assert engine._requests[vip].preemptions == 0
    # preemption-by-page-pressure fired on the low-priority victims
    assert engine.kv_stats()["evictions"] >= 1
    assert max(engine._requests[r].preemptions for r in victims) >= 1
    # admission backoff fired (next_admit_tick is only ever set by the
    # hold-off path; admit_attempts resets to 0 on the admission that
    # finally lands) and the retried request still completed
    assert engine._requests[backoff].next_admit_tick > 0
    assert engine.outcomes[backoff] == "ok" and len(res[backoff]) == 8
    # sustained pressure tripped shed mode and dropped sub-priority work
    assert stats["shed_mode_ticks"] >= 1
    assert counts["shed"] >= 1
    assert all(engine.outcomes[r] in ("ok", "shed") for r in doomed)
    engine.check_kv()                      # no page leaked through any path


def test_dense_deadline_timeout():
    """The dense path honours deadlines too: queued requests past deadline
    never start; a decoding slot past deadline frees with its partial
    output."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine("qwen3-4b", slots=1, max_len=48,
                                 max_new=10)
    rng = np.random.default_rng(0)
    slow = engine.submit(rng.integers(0, vocab, 8).astype(np.int32),
                         deadline=3)
    queued = engine.submit(rng.integers(0, vocab, 8).astype(np.int32),
                           deadline=2)            # expires before a slot frees
    ok = engine.submit(rng.integers(0, vocab, 8).astype(np.int32))
    res = engine.run()
    assert engine.outcomes[slow] == "timeout" and 0 < len(res[slow]) < 10
    assert engine.outcomes[queued] == "timeout" and res[queued] == []
    assert engine.outcomes[ok] == "ok" and len(res[ok]) == 10


# ---------------------------------------------------------------------------
# sampling: temperature + top-k (seeded host RNG)
# ---------------------------------------------------------------------------

def test_sampling_seeded_replayable_and_topk1_greedy():
    """Sampled decode is deterministic for a fixed (seed, trace) pair,
    top_k=1 collapses to greedy regardless of temperature, and the
    temperature=0 default is untouched argmax decode."""
    from repro.launch.serve import build_engine

    def serve(**kw):
        engine, vocab = build_engine("qwen3-4b", slots=2, max_len=48,
                                     max_new=4, **kw)
        rng = np.random.default_rng(3)
        for i in range(3):
            engine.submit(rng.integers(0, vocab, 5 + 2 * i).astype(np.int32))
        return engine.run()

    greedy = serve()
    assert serve() == greedy                       # greedy is deterministic
    hot1 = serve(temperature=0.9, top_k=8, sample_seed=11)
    hot2 = serve(temperature=0.9, top_k=8, sample_seed=11)
    assert hot1 == hot2                            # same seed -> same trace
    assert hot1.keys() == greedy.keys()
    assert all(len(v) == 4 for v in hot1.values())
    # top_k=1 == argmax even at high temperature
    assert serve(temperature=5.0, top_k=1, sample_seed=7) == greedy


def test_sampling_paged_mode_seeded():
    """The paged engine samples through the same seeded picker (prefill
    final token + decode ticks)."""
    from repro.launch.serve import build_engine

    def serve(seed):
        engine, vocab = build_engine("qwen3-4b", slots=2, max_len=48,
                                     max_new=4, kv_mode="paged", page_size=8,
                                     temperature=0.7, top_k=4,
                                     sample_seed=seed)
        rng = np.random.default_rng(5)
        for i in range(3):
            engine.submit(rng.integers(0, vocab, 6 + i).astype(np.int32))
        return engine.run()

    assert serve(seed=2) == serve(seed=2)


# ---------------------------------------------------------------------------
# prefix cache: differential correctness (cache-on == cache-off, exactly)
# ---------------------------------------------------------------------------

def _prefix_prompts(vocab, n=5, prefix_len=16, seed=7):
    """n prompts sharing a `prefix_len`-token common prefix (two full
    pages at page_size=8) with short random suffixes."""
    rng = np.random.default_rng(seed)
    common = rng.integers(0, vocab, prefix_len)
    return [np.concatenate(
        [common, rng.integers(0, vocab, int(rng.integers(3, 10)))]
    ).astype(np.int32) for _ in range(n)]


def _serve_cached(arch, kv_mode, prompts, prefix_cache, **kw):
    from repro.launch.serve import build_engine
    engine, vocab = build_engine(arch, slots=2, max_len=64, max_new=6,
                                 kv_mode=kv_mode, page_size=8,
                                 prefix_cache=prefix_cache, **kw)
    for p in prompts:
        engine.submit(p)
    return engine.run(), engine


@pytest.mark.parametrize("kv_mode", ["paged", "paged_int8"])
def test_prefix_cache_differential_token_exact(kv_mode):
    """Shared-prefix requests served THROUGH the radix cache produce
    token-identical outputs to the cold path (cache disabled) — the
    matched prefix's KV pages really are the same computation."""
    vocab = 256
    prompts = _prefix_prompts(vocab)
    hot, eng = _serve_cached("qwen3-4b", kv_mode, prompts, True)
    cold, _ = _serve_cached("qwen3-4b", kv_mode, prompts, False)
    assert hot == cold
    st = eng.prefix_stats()
    assert st["hits"] >= 3 and st["matched_tokens"] > 0
    assert eng.kv.stats()["shares"] >= 2      # >= one 2-page shared mapping
    eng.check_kv()


def test_prefix_cache_matches_dense_golden():
    """The cached paged path stays exactly equal to the DENSE engine (the
    no-pool golden): dense == paged(cache off) == paged(cache on)."""
    vocab = 256
    prompts = _prefix_prompts(vocab, seed=11)
    dense, _ = _serve_cached("qwen3-4b", "dense", prompts, False)
    hot, eng = _serve_cached("qwen3-4b", "paged", prompts, True)
    assert hot == dense
    assert eng.prefix_stats()["hits"] >= 1


def test_prefix_cache_cow_divergence_matches_cold():
    """A prompt diverging MID-PAGE from a cached sequence triggers
    copy-on-write (private copy of the partially matched page) and still
    decodes token-identically to the cold path."""
    from repro.launch.serve import build_engine
    rng = np.random.default_rng(13)
    vocab = 256
    common = rng.integers(0, vocab, 16)
    a = np.concatenate([common, rng.integers(0, vocab, 6)]).astype(np.int32)
    b = np.concatenate([common[:10],                   # diverge at token 10
                        rng.integers(0, vocab, 8)]).astype(np.int32)

    def serve_seq(prefix_cache):
        engine, _ = build_engine("qwen3-4b", slots=2, max_len=64, max_new=6,
                                 kv_mode="paged", page_size=8,
                                 prefix_cache=prefix_cache)
        engine.submit(a)
        engine.run()                  # a finishes -> pages enter the trie
        engine.submit(b)
        return engine.run(), engine

    hot, eng = serve_seq(True)
    cold, _ = serve_seq(False)
    assert hot == cold
    assert eng.cow_copies >= 1                   # the device copy ran
    assert eng.prefix_stats()["cow_count"] >= 1
    # b matched one full page + 2 tokens of the diverging page
    assert eng._requests[1].matched_tokens == 10
    eng.check_kv()


def test_prefix_cache_page_dedup_under_shared_load():
    """With many live shared-prefix requests, the pool holds each prefix
    page ONCE (refcount > 1) — the dedup the traffic benchmark measures."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine("qwen3-4b", slots=4, max_len=64, max_new=4,
                                 kv_mode="paged", page_size=8)
    prompts = _prefix_prompts(vocab, n=6, seed=23)
    for p in prompts:
        engine.submit(p)
    shared_seen = 0
    while engine.pending():
        engine.step()
        shared_seen = max(shared_seen, engine.kv.stats()["pages_shared"])
    assert shared_seen >= 2        # both prefix pages lived shared at once
    engine.check_kv()


def test_token_streaming_matches_batch_run():
    """The per-request stream() generators, consumed interleaved, drive
    the same continuous-batching ticks and yield exactly the tokens the
    batch run() API returns."""
    from repro.launch.serve import build_engine

    def build(submit_all=True):
        engine, vocab = build_engine("qwen3-4b", slots=2, max_len=48,
                                     max_new=5, kv_mode="paged", page_size=8)
        rng = np.random.default_rng(31)
        rids = [engine.submit(rng.integers(0, vocab, 7 + i).astype(np.int32))
                for i in range(3)]
        return engine, rids

    engine, rids = build()
    golden = engine.run()

    engine, rids = build()
    gens = {rid: engine.stream(rid) for rid in rids}
    got = {rid: [] for rid in rids}
    live = dict(gens)
    while live:                      # round-robin the consumers
        for rid, g in list(live.items()):
            try:
                got[rid].append(next(g))
            except StopIteration:
                del live[rid]
    assert got == golden


# ---------------------------------------------------------------------------
# regression: preemption of a request holding SHARED prefix pages
# ---------------------------------------------------------------------------

def test_preemption_shared_prefix_pages_only_decref():
    """Eviction under page pressure used to assume the victim owned its
    pages exclusively and returned them all to the free list; a victim
    whose leading pages are radix-cache mappings shared with the trie and
    a live peer must only DROP ITS REFERENCES — the peer keeps decoding
    from the same physical pages and the cache stays intact."""
    kv = BlockPoolKV(_kvcfg(num_slots=3, num_pages=17))
    pc = RadixPrefixCache(kv)
    prefix = list(range(16))                      # two full pages
    kv.ensure(0, 16)
    kv.advance(0, 16)
    pc.insert(prefix, kv.slot_pages(0), 16)
    kv.free_slot(0)

    sched = PhaseScheduler(SchedulerConfig(num_slots=3))
    r1 = Request(rid=1, prompt=np.asarray(prefix + [7, 8], np.int32),
                 arrival=0, max_new_tokens=4)
    r2 = Request(rid=2, prompt=np.asarray(prefix + [9], np.int32),
                 arrival=1, max_new_tokens=4)
    sched.submit(r1)
    sched.submit(r2)
    assert len(sched.admit(kv, prefix=pc)) == 2
    shared = [int(p) for p in kv.slot_pages(r1.slot)[:2]]
    assert shared == [int(p) for p in kv.slot_pages(r2.slot)[:2]]
    assert all(kv.refcount[p] == 3 for p in shared)   # trie + r1 + r2
    r2_pages = kv.slot_pages(r2.slot)
    free_before = kv.free_pages

    sched._evict(kv, r1)                          # preempt the sharer
    # ONLY r1's references dropped: shared pages never hit the free list
    assert all(kv.refcount[p] == 2 for p in shared)
    assert kv.slot_pages(r2.slot) == r2_pages     # peer untouched
    # exactly r1's PRIVATE pages came back (prompt 18 tokens -> 3 pages
    # + 1 headroom, minus the 2 shared)
    assert kv.free_pages == free_before + 2
    assert pc.match(prefix + [55]).matched_full == 16   # cache intact
    pc.check_invariants()
    # drain: peer finishes, trie evicts -> pool returns to empty
    sched.finish(kv, r2)
    assert all(kv.refcount[p] == 1 for p in shared)
    pc.evict(100)
    assert kv.free_pages == kv.cfg.total_pages - 1


def test_deadline_eviction_shared_prefix_pages_only_decref():
    """The deadline-expiry path must obey the same sharing contract as
    preemption: a timed-out request whose leading pages are radix-cache
    mappings shared with a live peer only DROPS ITS REFERENCES — exactly
    its private pages return to the free list, the peer's mapping and the
    trie are untouched, and a waiting expiree releases nothing (it never
    held pages)."""
    kv = BlockPoolKV(_kvcfg(num_slots=2, num_pages=17))
    pc = RadixPrefixCache(kv)
    prefix = list(range(16))                      # two full shared pages
    kv.ensure(0, 16)
    kv.advance(0, 16)
    pc.insert(prefix, kv.slot_pages(0), 16)
    kv.free_slot(0)

    sched = PhaseScheduler(SchedulerConfig(num_slots=2))
    doomed = Request(rid=1, prompt=np.asarray(prefix + [7, 8], np.int32),
                     arrival=0, max_new_tokens=4, deadline_tick=5)
    peer = Request(rid=2, prompt=np.asarray(prefix + [9], np.int32),
                   arrival=1, max_new_tokens=4)
    queued = Request(rid=3, prompt=np.asarray(prefix + [4], np.int32),
                     arrival=2, max_new_tokens=4, deadline_tick=5)
    sched.submit(doomed)
    sched.submit(peer)
    sched.submit(queued)                          # both slots taken: waits
    assert len(sched.admit(kv, prefix=pc)) == 2
    shared = [int(p) for p in kv.slot_pages(doomed.slot)[:2]]
    assert shared == [int(p) for p in kv.slot_pages(peer.slot)[:2]]
    assert all(kv.refcount[p] == 3 for p in shared)   # trie + both slots
    peer_pages = kv.slot_pages(peer.slot)
    free_before = kv.free_pages

    expired = sched.expire_deadlines(kv, now=6)
    assert sorted(r.rid for r in expired) == [1, 3]
    # ONLY the expiree's references dropped; the shared pages never hit
    # the free list and the peer decodes on from the same physical pages
    assert all(kv.refcount[p] == 2 for p in shared)
    assert kv.slot_pages(peer.slot) == peer_pages
    # doomed's 18-token prompt mapped 3 pages + 1 headroom; 2 were shared,
    # so exactly its 2 PRIVATE pages come back (the waiting expiree adds 0)
    assert kv.free_pages == free_before + 2
    assert pc.match(prefix + [55]).matched_full == 16   # cache intact
    pc.check_invariants()
    sched.finish(kv, peer)
    pc.evict(100)
    assert kv.free_pages == kv.cfg.total_pages - 1


# ---------------------------------------------------------------------------
# scheduler fuzz: random arrival/length/priority streams
# ---------------------------------------------------------------------------

def _fuzz_scheduler_trace(seed, n_requests=None, ticks_cap=4000):
    """Host-level lifecycle sim mirroring the engine's tick loop (no jax):
    random arrivals/lengths/priorities/deadlines with the prefix cache in
    the loop, invariant-checked every tick.  Returns outcome counts."""
    rng = np.random.default_rng(seed)
    num_pages = int(rng.integers(10, 22))
    kv = BlockPoolKV(PagedKVConfig(num_slots=3, max_len=48, page_size=8,
                                   num_pages=num_pages))
    pc = RadixPrefixCache(kv)
    sched = PhaseScheduler(SchedulerConfig(
        num_slots=3, prefill_chunk=8, prefill_token_budget=16,
        max_admission_retries=int(rng.integers(0, 3)),
        admission_backoff=int(rng.integers(0, 3))))
    n_requests = n_requests or int(rng.integers(4, 14))
    common = rng.integers(0, 4, 12).tolist()      # tiny vocab: collisions
    pending = []
    for rid in range(n_requests):
        plen = int(rng.integers(2, 20))
        prompt = rng.integers(0, 4, plen).tolist()
        if rng.random() < 0.5:                    # half share a prefix
            k = min(plen - 1, int(rng.integers(1, 13)))
            prompt[:k] = common[:k]
        pending.append((int(rng.integers(0, 12)), Request(
            rid=rid, prompt=np.asarray(prompt, np.int32),
            priority=int(rng.integers(0, 3)), arrival=rid,
            max_new_tokens=int(rng.integers(1, 7)),
            deadline_tick=None if rng.random() < 0.7
            else int(rng.integers(4, 40)))))
    outcomes = {}

    def finish(req):
        n = int(kv.lengths[req.slot])
        seq = list(req.prompt) + req.generated
        pc.insert(seq[:n], kv.slot_pages(req.slot), n)
        outcomes[req.rid] = "ok"
        sched.finish(kv, req)

    tick = 0
    while pending or sched.has_work:
        tick += 1
        assert tick < ticks_cap, "scheduler starved a request"
        while pending and pending[0][0] <= tick:
            sched.submit(pending.pop(0)[1])
        for req in sched.expire_deadlines(kv, tick):
            outcomes[req.rid] = "timeout"
        admitted = sched.admit(kv, now=tick, prefix=pc)
        for req in admitted:
            PhaseScheduler._drop_cow(kv, req)     # "engine" copies at once
        for req in sched.drain_shed():
            outcomes[req.rid] = "shed"
        sched.ensure_decode_pages(kv)
        decoding = sched.decoding()
        for job in sched.prefill_jobs():
            kv.advance(job.req.slot, job.count)
            sched.finish_prefill_chunk(job.req, job.count)
            if job.req.phase is Phase.DECODE:
                job.req.generated.append(int(rng.integers(0, 4)))
                if job.req.n_generated >= job.req.max_new_tokens:
                    finish(job.req)
        for req in decoding:
            if req.slot < 0 or sched._active.get(req.slot) is not req:
                continue                          # evicted this tick
            kv.advance(req.slot, 1)
            req.generated.append(int(rng.integers(0, 4)))
            if req.n_generated >= req.max_new_tokens:
                finish(req)
        pc.check_invariants()
    # accounting: every submitted request reached exactly one outcome
    assert sorted(outcomes) == list(range(n_requests))
    # drain the cache: every page accounted for, none leaked
    pc.evict(10 ** 6)
    assert kv.free_pages == kv.cfg.total_pages - 1
    return outcomes


def test_scheduler_fuzz_seeded_sweep():
    for seed in range(60):
        _fuzz_scheduler_trace(seed)


def test_scheduler_fuzz_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=200, deadline=None)
    @hyp.given(seed=st.integers(0, 2 ** 31 - 1), n=st.integers(1, 14))
    def drive(seed, n):
        _fuzz_scheduler_trace(seed, n_requests=n)

    drive()


def test_engine_fuzz_outcomes_account_for_every_request():
    """End-to-end randomized run on the real engine: arrivals with mixed
    priorities/deadlines under a small pool — `engine.outcomes` must cover
    every submitted rid exactly once and pool+trie invariants must hold."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine(
        "qwen3-4b", slots=2, max_len=48, max_new=4, kv_mode="paged",
        page_size=8, num_pages=11, max_admission_retries=3,
        admission_backoff=1)
    rng = np.random.default_rng(17)
    rids = []
    for i in range(6):
        rids.append(engine.submit(
            rng.integers(0, vocab, int(rng.integers(3, 14))).astype(np.int32),
            priority=int(rng.integers(0, 3)),
            deadline=None if i % 3 else 60))
    res = engine.run()
    assert sorted(engine.outcomes) == sorted(rids)
    counts = engine.degradation_stats()
    assert counts["ok"] + counts["timeout"] + counts["shed"] == len(rids)
    assert sorted(res) == sorted(rids)
    assert all(len(res[r]) <= 4 for r in rids)
    engine.check_kv()


@pytest.mark.parametrize("kv_mode", ["paged", "paged_int8"])
def test_engine_step_donates_pool(kv_mode):
    """The step program takes the engine's pool as a donated argument: a
    tick updates it in place, so the arrays the engine held before the
    tick are deleted and no second pool is ever live."""
    from repro.launch.serve import build_engine
    engine, vocab = build_engine("qwen3-4b", slots=2, max_len=48, max_new=4,
                                 kv_mode=kv_mode, page_size=8)
    for p in _prompts(vocab, n=3):
        engine.submit(p)
    while engine.pending():
        before = list(engine.pool.values())
        engine.step()
        assert all(x.is_deleted() for x in before)
        assert not any(x.is_deleted() for x in engine.pool.values())
    engine.check_kv()


def test_pool_page_copies_donate_pool():
    """Copy-on-write and a migrated page's landing update the pool in
    place: the pool given is deleted, and only the target page of every
    layer changes."""
    from repro.serving import engine as serving_engine
    rng = np.random.default_rng(5)

    def pool():
        return {k: jnp.asarray(rng.normal(size=(3, 6, 4, 2, 8)), jnp.float32)
                for k in ("k", "v")}

    src = pool()
    want = {k: np.array(v) for k, v in src.items()}   # copies: no view
    out = serving_engine._copy_pool_page(src, jnp.int32(2), jnp.int32(5))
    assert all(x.is_deleted() for x in src.values())
    for k, v in want.items():
        v[:, 5] = v[:, 2]
        np.testing.assert_array_equal(np.asarray(out[k]), v)
    vals = {k: jnp.asarray(rng.normal(size=(3, 4, 2, 8)), jnp.float32)
            for k in ("k", "v")}
    landed = serving_engine._write_pool_page(out, jnp.int32(1), vals)
    assert all(x.is_deleted() for x in out.values())
    for k, v in want.items():
        v[:, 1] = np.asarray(vals[k])
        np.testing.assert_array_equal(np.asarray(landed[k]), v)
