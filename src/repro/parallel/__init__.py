from .sharding import param_specs, cache_specs, make_shardings
from .ring_matmul import ring_matmul, ring_matmul_ref
from .ring_attention import ring_attention
from .pipeline import pipeline_forward

__all__ = ["param_specs", "cache_specs", "make_shardings",
           "ring_matmul", "ring_matmul_ref", "ring_attention",
           "pipeline_forward"]
