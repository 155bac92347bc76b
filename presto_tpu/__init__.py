"""presto_tpu: a TPU-native distributed SQL query engine.

A from-scratch framework with the capabilities of Presto (reference:
arhimondr/presto), built idiomatically for JAX/XLA/TPU:

- Columnar batches are fixed-capacity padded device arrays with validity
  masks (reference: presto-common Page.java:33 / Block.java:24), so
  filters are mask-ANDs and XLA never sees a dynamic shape.
- Presto's runtime bytecode generation (presto-bytecode +
  presto-main sql/gen/ExpressionCompiler.java:56) is replaced by tracing
  a RowExpression IR into jax-jittable functions compiled by XLA.
- The hash-repartitioning shuffle (PartitionedOutputOperator.java:52 +
  HTTP exchange) becomes `jax.lax.all_to_all` over an ICI device mesh.
"""

import jax

# SQL semantics need exact 64-bit integer arithmetic (BIGINT, DECIMAL as
# scaled int64); enable before any array is created. Nothing here
# touches a backend: importing the package must not take the chip (one
# process per chip — a launcher that imports presto_tpu may still start
# a child that needs it). The persistent compilation cache is decided
# when the first LocalRunner/Coordinator is built
# (execution/compile_cache.configure).
jax.config.update("jax_enable_x64", True)

from presto_tpu.types import (  # noqa: E402
    BIGINT, INTEGER, SMALLINT, TINYINT, DOUBLE, REAL, BOOLEAN, VARCHAR,
    DATE, TIMESTAMP, UNKNOWN, DecimalType, Type, decimal_type,
)
from presto_tpu.batch import Batch, Column  # noqa: E402

__version__ = "0.1.0"
