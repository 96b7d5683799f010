"""A whole run at smoke size on the CPU, past the harness's look for a
chip: sound, it is correct; with the timed path broken underneath, it is
not.  The faults a serving cell can have: a token altered where it is
produced, and a step that returns its state (the KV pool) unchanged."""
import time

import jax
import pytest

import smoke_root
import harness
import run as bench_run
from repro.serving import engine as serving_engine


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke_root.make(str(tmp_path_factory.mktemp("smoke")))


def _run(root, name, seed=2**31 + 3):
    cell = harness.Cell(name, root)
    res, ok = bench_run.execute(cell, seed, 1.5, False, jax.devices()[:1],
                                time.monotonic())
    return res, ok


def _broken(monkeypatch, fault):
    step = serving_engine._pick_step

    def pick(fn, params, tokens, pool, pt, lens, counts):
        rows, picked, new_pool = step(fn, params, tokens, pool, pt, lens,
                                      counts)
        if fault == "token":
            picked = (picked + 1) % rows.shape[-1]
        else:
            new_pool = pool
        return rows, picked, new_pool

    monkeypatch.setattr(serving_engine, "_pick_step", pick)


@pytest.mark.parametrize("mix", ["smoke-chat", "smoke-docqa"])
def test_sound_run_is_correct(root, mix):
    res, ok = _run(root, f"qwen3-smoke.{mix}")
    assert ok and res["correct"]
    assert res["checks"]["served_tokens_compared"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", ["token", "state"])
@pytest.mark.parametrize("mix", ["smoke-chat", "smoke-docqa"])
def test_broken_path_is_not_correct(root, mix, fault, monkeypatch):
    _broken(monkeypatch, fault)
    res, ok = _run(root, f"qwen3-smoke.{mix}")
    assert not ok and not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_is_not_correct(root):
    """The control (the reference in fp8 in the program's place) reads a
    gap above the limit on the served tokens of a sound run."""
    cell = harness.Cell("qwen3-smoke.smoke-chat", root)
    ref = cell.reference()
    fam = cell.family()
    bundle = fam.program_bundle(cell.config)
    p = fam.make_params(bundle, harness.jax_key(11))
    import numpy as np
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, 256, 40).astype(np.int32),
             rng.integers(0, 256, 24).astype(np.int32)) for _ in range(8)]
    greedy = []
    for prompt, _ in reqs:        # the reference's own greedy tokens
        seq = list(prompt)
        for _ in range(24):
            lg = ref.logits_at(cell.config, p, [np.asarray(seq)],
                               [np.asarray([len(seq) - 1])])[0]
            seq.append(int(lg[0].argmax()))
        greedy.append((prompt, np.asarray(seq[40:], np.int32)))
    exact = max(g.max() for g in ref.served_gaps(cell.config, p, greedy))
    control = max(g.max() for g in ref.served_gaps(cell.config, p, greedy,
                                                   quant="fp8"))
    limit = cell.traffic["check"]["max_logit_gap"]
    assert exact <= limit < control
