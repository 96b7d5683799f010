"""Production meshes. Functions, not module constants — importing this file
never touches jax device state."""
from __future__ import annotations

import os

from repro.runtime import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (CPU tests/benches)."""
    return compat.make_mesh((1, 1), ("data", "model"))


def make_host_mesh(n: int | None = None):
    """(1, n) mesh over the first ``n`` devices of this host (all of them
    when ``n`` is None): the model axis spans the n chips, so parameters
    shard tensor-parallel and long sequences take the ring
    (``RingAttnPolicy``) across them."""
    import jax
    local = jax.local_devices()
    n = len(local) if n is None else n
    if not 1 <= n <= len(local):
        raise ValueError(f"a host mesh of {n} devices; this host has "
                         f"{len(local)}")
    return compat.make_mesh((1, n), ("data", "model"), devices=local[:n])


def make_worker_mesh():
    """1-device mesh over THIS process's first local device.

    A fleet worker must not use ``make_local_mesh``: once
    ``jax.distributed.initialize`` has run, ``jax.devices()`` is global
    and a (1, 1) device mesh would place every rank's compute on process
    0's device.  Built from ``jax.local_devices()`` the mesh stays on the
    rank's own device whether or not the coordinator is up."""
    import jax
    return compat.make_mesh((1, 1), ("data", "model"),
                            devices=jax.local_devices()[:1])


def refuse_gang_on_tpu(nprocs: int) -> None:
    """Raise unless ``nprocs`` JAX worker processes can coexist here.

    A chip belongs to one process at a time, and every JAX worker would
    claim all of this host's TPU chips; the second would fail or hang.
    Workers whose ``JAX_PLATFORMS`` list leaves out ``tpu`` (for example
    ``cpu``) never touch the chips."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    listed = {p.strip().lower() for p in platforms.split(",") if p.strip()}
    if nprocs <= 1 or (listed and "tpu" not in listed):
        return
    chips = compat.tpu_chips_attached()
    if chips:
        raise RuntimeError(
            f"refusing to start {nprocs} JAX worker processes on a host "
            f"with {chips} TPU chip(s): each worker would claim every "
            "chip. Run one process per host (N=1), or set "
            "JAX_PLATFORMS=cpu to run the workers on the CPU.")
