"""starrocks_tpu — a TPU-native, vectorized, MPP-parallel OLAP SQL engine.

A from-scratch JAX/XLA/Pallas re-design with the capabilities of StarRocks
(reference: /root/reference — Java FE + C++ BE). The columnar Chunk model
(reference: be/src/column/chunk.h:66) becomes static-shaped struct-of-array
device buffers; the vectorized pipeline engine (be/src/exec/) becomes compiled
mesh programs; hash-partition exchange (be/src/exec/pipeline/exchange/) maps to
lax.all_to_all over the TPU ICI mesh.

Subpackages
-----------
- ``types``     logical type system (reference: be/src/types/logical_type.h:27)
- ``column``    columnar chunk model (reference: be/src/column/)
- ``exprs``     vectorized expression engine (reference: be/src/exprs/)
- ``ops``       relational operators (reference: be/src/exec/)
- ``parallel``  mesh sharding + exchange (reference: be/src/exec/pipeline/exchange/)
- ``sql``       parser/analyzer/optimizer/planner (reference: fe/fe-core/.../sql/)
- ``storage``   catalog + tablet storage (reference: be/src/storage/)
- ``runtime``   session, executor, profile, config (reference: be/src/common/, exec/runtime/)
"""

import os

import jax

# The engine needs 64-bit ints for DECIMAL arithmetic (scaled int64) and
# DATETIME microseconds; enable before any tracing happens.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compile cache, placed here and nowhere else: engine,
# servers, tests and tools all import this package before they compile.
# JAX reads JAX_COMPILATION_CACHE_DIR itself, so when it is set no code
# names another directory; otherwise the one fixed, normalised path
# <checkout>/.xla_cache (a cache in a directory that moves never hits).
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".xla_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

__version__ = "0.1.0"
