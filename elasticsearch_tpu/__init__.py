"""elasticsearch_tpu — a TPU-native distributed search & analytics engine.

A ground-up rebuild of the capabilities of Elasticsearch 2.0 (reference:
/root/reference, surveyed in SURVEY.md) designed for TPUs: per-shard inverted
indexes and columnar fielddata live as dense device tensors, BM25 scoring and
aggregations are batched XLA/Pallas programs, and cross-shard reduces are mesh
collectives (jax.lax.top_k / psum) instead of coordinator-side merge loops.

Layer map (mirrors SURVEY.md §1):
  common/    — settings, circuit breakers, wire/json helpers       (ref L0)
  analysis/  — tokenizers, token filters, analyzers                (ref index/analysis)
  mapping/   — schema: field types, dynamic mapping                (ref index/mapper)
  index/     — tensor segments, engine, translog, shards           (ref index/engine, translog, shard)
  ops/       — device kernels: BM25 scoring, top-k, segment ops    (replaces Lucene's hot loops)
  search/    — query DSL compilation, query/fetch phases, aggs     (ref index/query, search/)
  parallel/  — mesh, doc routing, cross-shard collective reduce    (ref cluster/routing, SearchPhaseController)
  cluster/   — cluster state, routing table, allocation, service   (ref cluster/)
  rest/      — HTTP REST API surface                               (ref rest/, http/)
"""

# Exact integer semantics for longs/dates (epoch millis) require 64-bit device
# types; we enable x64 globally and pass explicit dtypes everywhere hot
# (scores are always float32/bfloat16, ids int32).
import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: serving shapes are pow2-bucketed
# (serving/packed_view.py), so the compile set is small and stable — caching
# it on disk makes cold-start p99 a one-time cost per machine instead of a
# per-process multi-second stall (ref: the reference warms searchers via
# indices/warmer/; here the "warm" artifact is the compiled executable).
# JAX reads JAX_COMPILATION_CACHE_DIR itself; only without it does the
# cache go to one fixed, git-ignored directory beside the package (the
# path is part of the cache key, so it never moves).
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".xla_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)

__version__ = "0.1.0"
