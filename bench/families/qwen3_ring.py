"""Qwen3 dense decoder trained on one host's chips: the program's bundle
cut to the configuration's depth and dtype and placed on the mesh its
``deployment`` states, seeded weights in the program's layout and
placement, and operation and byte counts from shapes.

The sharding plan is the program's own (``launch.mesh.make_host_mesh``,
``parallel.sharding.param_specs`` through ``ArchBundle.on_mesh``); this
file only selects it.  Importing it touches no device.
"""
from __future__ import annotations

import os

import harness

_qwen3 = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "qwen3.py"),
    "bench_family_qwen3")
sizes = _qwen3.sizes
linear_params = _qwen3.linear_params


def program_bundle(config: dict):
    """The program's bundle at the file's depth and dtype, on a host mesh
    of the deployment's chips.  Every other size is checked against the
    program's own configuration (``qwen3.program_bundle``), so a file
    that differs from the program is refused."""
    import dataclasses

    import jax.numpy as jnp

    from repro.launch.mesh import make_host_mesh
    c = config["config"]
    from repro.configs import get_bundle
    full = get_bundle(config["arch_id"],
                      smoke=config.get("program_preset") == "smoke")
    _qwen3.program_bundle(dict(config, config=dict(
        c, num_hidden_layers=full.cfg.n_layers)))
    cfg = dataclasses.replace(full.cfg, n_layers=c["num_hidden_layers"],
                              dtype=getattr(jnp, config["dtype"]))
    bundle = dataclasses.replace(full, cfg=cfg)
    return bundle.on_mesh(make_host_mesh(config["deployment"]["chips"]))


def make_params(bundle, key):
    """``qwen3.make_params``'s seeded weights, drawn in place on the
    bundle's mesh by the program's ``param_specs``."""
    import jax
    return jax.jit(lambda k: _qwen3.make_params(bundle, k),
                   out_shardings=bundle.param_shardings())(key)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------

def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward (three times
    the forward), without recomputation: 2 per multiply-add of the linear
    weights (``qwen3.linear_params``), and causal attention at S/2 keys a
    query, 4 * Dh per key and query head."""
    s = sizes(config)
    attention = 4.0 * s["L"] * s["H"] * s["Dh"] * seq_len / 2
    return 3.0 * (2.0 * linear_params(config) + attention)


def ring_hop_cost(config: dict, batch: int, seq_len: int, chips: int,
                  itemsize: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of the ring hops of one training step on one chip.

    Every hop folds one (S/m x S/m) score tile of the chip's queries
    against the visiting kv shard, for every query head, in every layer:
    the fold does not skip tiles that the causal mask empties.  Forward,
    4 * Dh FLOPs a tile entry (scores and the weighted sum of values);
    backward, 10 * Dh (the scores again, dP, dV, dQ and dK).  Bytes: each
    pass reads the chip's q rows and the visiting k and v once and writes
    its outputs once (forward o; backward dq and the carried dk, dv), and
    the backward also reads o and do; the logsumexp rows are left out.
    A recomputed forward is not counted."""
    s = sizes(config)
    L, H, Kv, Dh = s["L"], s["H"], s["Kv"], s["Dh"]
    S_l = seq_len // chips
    tiles = L * chips * batch * H * S_l * S_l
    flops = (4.0 + 10.0) * Dh * tiles
    q_rows = batch * S_l * H * Dh * itemsize
    kv_rows = batch * S_l * Kv * Dh * itemsize
    fwd = 2 * q_rows + 2 * kv_rows                  # q, k, v in; o out
    bwd = 4 * q_rows + 4 * kv_rows                  # q, o, do, k, v in;
    #                                                 dq, dk, dv out
    return flops, float(L * chips * (fwd + bwd))
