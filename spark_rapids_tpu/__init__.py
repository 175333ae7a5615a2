"""spark-rapids-tpu: a TPU-native columnar SQL execution framework.

A ground-up TPU redesign of the capabilities of NVIDIA's RAPIDS Accelerator
for Apache Spark (the reference implementation surveyed in SURVEY.md):
Arrow-layout columnar batches resident in TPU HBM as jax Arrays; expression
and operator kernels compiled by XLA; sort-based segmented
groupby/join/sort under a static-shape regime; a handle-based
HBM->host->disk spill framework with split-and-retry out-of-core
execution; and a partition-exchange shuffle with host-file and
ICI-collective transports.
"""
import os as _os

import jax as _jax

# SQL semantics require 64-bit ints/floats (LongType, DoubleType, decimal64,
# timestamps); enable before any array is created.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: query-shaped programs are large and a
# cold start compiles dozens of them; caching across processes turns the
# second start into seconds. The place is JAX's own to give: where
# JAX_COMPILATION_CACHE_DIR is set, no directory is set in code at all;
# otherwise it is the one fixed path <checkout>/.jax_cache (the path is
# part of the cache key, so it never moves). SRTPU_COMPILE_CACHE has one
# meaning only: the value "0" switches the cache and the warm pack off.
# Any other value of it does NOT place the cache.
if _os.environ.get("SRTPU_COMPILE_CACHE") != "0":
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _cache = _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache")
        _os.makedirs(_cache, exist_ok=True)
        _jax.config.update("jax_compilation_cache_dir", _cache)
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

from .columnar import dtypes
from .columnar.column import Column
from .columnar.table import Table, Schema, Field
from .config import TpuConf
from .session import TpuSession, DataFrame
from . import functions

__version__ = "0.1.0"
__all__ = ["TpuSession", "DataFrame", "Table", "Column", "Schema", "Field",
           "TpuConf", "functions", "dtypes"]
