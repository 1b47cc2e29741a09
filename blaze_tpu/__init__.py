"""blaze-tpu: a TPU-native columnar query-execution framework.

Provides the capabilities of Apache Auron (formerly kwai/blaze) — a Spark
physical-plan accelerator — re-designed TPU-first: the plan IR is executed as
columnar programs on TPU via JAX/XLA/Pallas, with fixed-shape batch tiling,
spill-aware memory management, and shuffle exchanges that map to ICI
``all_to_all`` across a TPU mesh.

Layer map (mirrors the reference's layering, see SURVEY.md §1):

- ``blaze_tpu.ir``      — plan/expression IR, the wire contract
                          (reference: ``native-engine/auron-serde/proto/auron.proto``)
- ``blaze_tpu.core``    — columnar batch representation on TPU
                          (reference: Arrow RecordBatch + ``datafusion-ext-commons``)
- ``blaze_tpu.exprs``   — expression compiler: IR -> jax-traceable fns
                          (reference: ``datafusion-ext-exprs``, ``-functions``)
- ``blaze_tpu.ops``     — operators, one per plan-IR node
                          (reference: ``datafusion-ext-plans``)
- ``blaze_tpu.runtime`` — per-task execution runtime, memory manager, metrics
                          (reference: ``native-engine/auron`` + ``memmgr``)
- ``blaze_tpu.parallel``— device-mesh exchange (ICI collectives), distributed exec
                          (reference: shuffle transport / Spark BlockManager)
- ``blaze_tpu.io``      — batch serde, compression, file formats
                          (reference: ``datafusion-ext-commons/src/io``)
"""

import os as _os

import jax

# A SQL engine is 64-bit native: BIGINT, DOUBLE, timestamps-as-micros and the
# spark-exact xxhash64 all require real int64/float64 arithmetic.
jax.config.update("jax_enable_x64", True)


def setup_compile_cache():
    """Place JAX's persistent compilation cache: operator kernels recur
    across processes (shapes come from capacity buckets), and a cold run
    otherwise compiles every one of them again.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and
    the directory is left alone. Otherwise the cache lives at one fixed path
    inside the checkout — the path is part of the cache key, so it must not
    move between processes. Runs at import, before anything can compile."""
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


setup_compile_cache()

__version__ = "0.2.0"
