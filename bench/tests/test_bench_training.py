"""The training cell at smoke size on the CPU: the float32 reference
agrees with the program's step, a whole run past the harness's look for a
chip is correct, and with the timed path broken underneath it is not.
The faults a one-chip training cell can have: a step that returns its
state unchanged, and half of the batch left out with the mean taken over
the rest.  The bfloat16 control departs."""
import time

import jax
import jax.numpy as jnp
import pytest

import smoke_root
import harness
import run as bench_run
import training

CELL = "mamba2-smoke.smoke-train"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke_root.make(str(tmp_path_factory.mktemp("smoke")))


def _run(root, seed=2**31 + 5):
    cell = harness.Cell(CELL, root)
    return bench_run.execute(cell, seed, 1.0, False, jax.devices()[:1],
                             time.monotonic())


def test_reference_agrees_with_the_program(root):
    cell = harness.Cell(CELL, root)
    fam, bundle, step, params, opt, batch = training.build(cell, 7)
    _, _, prog = training.first_steps(cell, fam, bundle, step, params, opt,
                                      batch, 7)
    ref = training.reference_readings(cell, 7)
    g = training.gaps(prog, ref)
    assert g["loss_gap"] < 1e-6, g
    assert g["grad_norm_gap"] < 1e-4 and g["update_norm_gap"] < 1e-4, g
    assert len(prog["grad_norms"]) == len(ref["grad_norms"]) == 12


def test_sound_run_is_correct(root):
    res, ok = _run(root)
    assert ok and res["correct"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(res["checks"]) == set(training.CHECKS)


def _broken(monkeypatch, fault):
    from repro import training as program
    make = program.make_train_step

    def make_broken(forward, hyper):
        step = make(forward, hyper)

        def broken(params, opt_state, batch, grad_scale=None):
            if fault == "state":
                new_p, new_o, m = step(params, opt_state, batch)
                return params, opt_state, m
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            return step(params, opt_state, half)
        return broken

    monkeypatch.setattr(program, "make_train_step", make_broken)


@pytest.mark.parametrize("fault", ["state", "half_batch"])
def test_broken_step_is_not_correct(root, fault, monkeypatch):
    _broken(monkeypatch, fault)
    res, ok = _run(root)
    assert not ok and not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_bf16_control_is_not_correct(root):
    """The reference in bfloat16 in the program's place fails a limit."""
    cell = harness.Cell(CELL, root)
    ref = training.reference_readings(cell, 9)
    low = training.reference_readings(cell, 9, dtype=jnp.bfloat16)
    g = training.gaps(low, ref)
    limits = cell.traffic["check"]["limits"]
    assert any(g[k] > limits[k] for k in limits), g


def test_batches_are_deterministic_and_every_row_differs(root):
    import numpy as np
    cell = harness.Cell(CELL, root)
    seed = 2**32 + 17
    a = training.data_fn(cell.config, cell.traffic, seed)
    b = training.data_fn(cell.config, cell.traffic, seed)
    c = training.data_fn(cell.config, cell.traffic, seed + 1)
    rows = []
    for i in (1, 2, 3):
        x, y = a(i), b(i)
        assert (np.asarray(x["tokens"]) == np.asarray(y["tokens"])).all()
        assert (np.asarray(x["tokens"])[:, 1:]
                == np.asarray(x["labels"])[:, :-1]).all()
        assert int(np.asarray(x["labels"]).max()) < \
            cell.config["token_vocab"]
        assert not (np.asarray(c(i)["tokens"])
                    == np.asarray(x["tokens"])).all()
        rows += [tuple(r) for r in np.asarray(x["tokens"]).tolist()]
    assert len(set(rows)) == len(rows) == 3 * cell.traffic["batch"]
