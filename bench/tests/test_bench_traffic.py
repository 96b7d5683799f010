"""The traffic generator: deterministic per seed, the same sizes for every
seed, the stated distributions, and a warm-up that covers every step
shape the lengths can reach."""
import os
import statistics

import numpy as np
import pytest

import smoke_root  # noqa: F401  (puts bench/ on the path)
import harness
import serving

VOCAB = 151936


def _traffic(name):
    return harness.load_json(harness.traffic_path(name))


def test_open_loop_is_deterministic_per_seed():
    t = _traffic("chat")
    a = serving.open_loop_plan(t, 30, VOCAB, 2**31 + 11)
    b = serving.open_loop_plan(t, 30, VOCAB, 2**31 + 11)
    c = serving.open_loop_plan(t, 30, VOCAB, 7)
    assert [(r.due, r.out_len, r.prompt.tolist()) for r in a] == \
        [(r.due, r.out_len, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


def test_window_has_the_same_sizes_for_every_seed():
    t = _traffic("chat")
    plans = [serving.open_loop_plan(t, 30, VOCAB, s) for s in (1, 2, 3)]
    win = [[r for r in p if r.measured] for p in plans]
    assert len({len(w) for w in win}) == 1
    assert len(win[0]) == round(t["rate_per_s"] * 30)
    for w in win[1:]:
        assert sorted(len(r.prompt) for r in w) == \
            sorted(len(r.prompt) for r in win[0])
        assert sorted(r.out_len for r in w) == \
            sorted(r.out_len for r in win[0])
    for w in win:
        due = [r.due for r in w]
        assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30


def test_stated_distributions():
    t = _traffic("chat")
    p = serving.quantile_sizes(t["prompt"], 2001)
    o = serving.quantile_sizes(t["output"], 2001)
    assert p.min() >= 64 and p.max() <= 1536
    assert o.min() >= 16 and o.max() <= 512
    assert abs(np.median(p) - 512) <= 1 and abs(np.median(o) - 128) <= 1
    # one sigma above the median, below the clip (1.8 sigma up)
    one_up = np.quantile(np.log(p / 512.0), statistics.NormalDist().cdf(1))
    assert abs(one_up - t["prompt"]["sigma"]) < 0.01
    gaps = serving.exponential_gaps(4.0, 4000)
    assert abs(gaps.mean() - 0.25) < 0.01
    assert abs(statistics.median(gaps) - np.log(2) / 4.0) < 1e-3


def test_sessions_are_deterministic_and_follow_the_mix():
    t = _traffic("docqa")
    a = serving.sessions(t, VOCAB, 5)
    b = serving.sessions(t, VOCAB, 5)
    for _ in range(30):
        sa, sb = next(a), next(b)
        assert [x.tolist() for x in sa] == [x.tolist() for x in sb]
        assert 3 <= len(sa) <= 5
        assert all(512 + 16 <= len(x) <= 6144 + 128 for x in sa)
        doc = min(len(x) for x in sa) - 128     # shorter than the document
        assert all((x[:doc] == sa[0][:doc]).all() for x in sa)


def _reached(traffic):
    """(T, view) shapes a single request of every length reaches, by
    simulating the chunked prefill and the decode, with or without a
    prefix hit at every page boundary."""
    eng = traffic["engine"]
    C, page, max_len = eng["prefill_chunk"], eng["page_size"], eng["max_len"]
    lo, hi = serving.prompt_range(traffic)
    per_slot = -(-max_len // page)

    def view(n):
        return min(per_slot, serving._pow2(-(-n // page)))

    out = set()
    for L in range(lo, hi + 1, 7):
        for start in ({0} | set(range(page, L, page * 29))):
            pos = start
            while pos < L:
                c = min(C, L - pos)
                out.add((serving._pow2(c), view(pos + c)))
                pos += c
        for n in (L + 1, min(max_len, L + eng["max_new_tokens"])):
            out.add((1, view(n)))
    return out


@pytest.mark.parametrize("mix", ["chat", "docqa"])
def test_warm_up_covers_every_reachable_shape(mix):
    t = _traffic(mix)
    warm = set(serving.warm_shapes(t))
    assert _reached(t) <= warm
    assert len(warm) < 64


def test_traffic_files_name_known_parameters():
    for f in os.listdir(os.path.join(smoke_root.BENCH, "traffic")):
        t = harness.load_json(os.path.join(smoke_root.BENCH, "traffic", f))
        if t["kind"] == "train":
            assert set(t["check"]["limits"]) == {"grad_norm_gap",
                                                 "update_norm_gap"}
            assert t["check"]["steps"] >= 1
            continue
        e = t["engine"]
        assert e["prefill_token_budget"] >= e["slots"] * e["prefill_chunk"]
        lo, hi = serving.prompt_range(t)
        assert hi + e["max_new_tokens"] <= e["max_len"]
