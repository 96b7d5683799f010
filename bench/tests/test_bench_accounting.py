"""The harness's interval accounting against the engine's own counters at
smoke size on the CPU: every prompt token is either computed or served by
the prefix cache, and every token after a request's first comes from one
decode row that read the prompt and the tokens before it."""
import numpy as np
import pytest

import smoke_root
import harness
import serving


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke_root.make(str(tmp_path_factory.mktemp("acct")))


@pytest.mark.parametrize("shared", [False, True])
def test_interval_agrees_with_the_engine(root, shared):
    cell = harness.Cell("qwen3-smoke.smoke-docqa", root)
    _, _, _, engine, _ = serving.setup(cell, 2**31 + 9, False, lambda m: None)
    rng = np.random.default_rng(4)
    doc = rng.integers(0, 256, 33).astype(np.int32)
    reqs = []
    for i in range(6):
        head = doc if shared else rng.integers(0, 256, 33).astype(np.int32)
        q = rng.integers(0, 256, 3 + i).astype(np.int32)
        reqs.append(serving.Req(np.concatenate([head, q]), 4))
    iv = serving.Interval()
    iv.begin(engine)
    for r in reqs[:3]:
        r.rid = engine.submit(r.prompt)
    later = reqs[3:]
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
    assert all(r.n == 4 for r in reqs)
    assert iv.prompt_computed + iv.matched == sum(len(r.prompt)
                                                  for r in reqs)
    assert (iv.matched > 0) == shared
    assert iv.decode_rows == sum(r.n - 1 for r in reqs)
    assert iv.decode_keys == sum(len(r.prompt) + k for r in reqs
                                 for k in range(1, r.n))
    assert iv.emitted == sum(r.n for r in reqs)
