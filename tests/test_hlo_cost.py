"""Scan-aware HLO cost parser vs known ground truth."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo_cost import module_cost


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_plain_matmul_matches_xla():
    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    comp = _compile(lambda a, b: a @ b, a, b)
    mine = module_cost(comp.as_text())
    assert mine.flops == pytest.approx(comp.cost_analysis()["flops"])
    assert mine.flops == pytest.approx(2 * 256 * 512 * 128)


def test_scan_multiplies_trip_count():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        out, _ = jax.lax.scan(body, x, None, length=48)
        return out
    comp = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    mine = module_cost(comp.as_text())
    assert mine.flops == pytest.approx(48 * 2 * 128 ** 3, rel=0.01)
    # XLA's own counter misses the trip count
    assert comp.cost_analysis()["flops"] < mine.flops / 10


def test_nested_scans_multiply():
    def f(x):
        def inner(c, _):
            return c @ c, None
        def outer(c, _):
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=6)
        return out
    comp = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    mine = module_cost(comp.as_text())
    assert mine.flops == pytest.approx(24 * 2 * 64 ** 3, rel=0.01)


def test_bytes_reasonable_for_elementwise():
    comp = _compile(lambda x: x + 1.0,
                    jax.ShapeDtypeStruct((1024, 1024), jnp.float32))
    mine = module_cost(comp.as_text())
    nbytes = 1024 * 1024 * 4
    assert nbytes <= mine.bytes <= 4 * nbytes
