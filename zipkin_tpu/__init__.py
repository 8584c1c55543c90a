"""zipkin-tpu: a TPU-native distributed-tracing analytics framework.

Re-implements the capability surface of Twitter Zipkin (reference:
/root/reference, Scala/Finagle) as an idiomatic JAX/XLA design:

- span ingest with backpressure + adaptive sampling (zipkin-collector,
  zipkin-sampler)
- a pluggable ``SpanStore`` SPI (zipkin-common storage traits) with an
  in-memory reference store and a device-resident columnar store
- trace query with slice intersection + time-skew-adjusted assembly
  (zipkin-query)
- streaming dependency-link aggregation, latency percentiles, top-k and
  cardinality served from on-device sketch state (zipkin-aggregate)
- a JSON/HTTP API mirroring zipkin-web's routes, and a vectorized
  tracegen benchmark harness (zipkin-tracegen)

The compute path is JAX (jit/shard_map); strings live in a host
dictionary encoder, the device sees only fixed-width integers/floats.
"""

import os as _os

if _os.environ.get("ZIPKIN_TPU_X64", "1") != "0":
    # 64-bit trace/span ids and µs timestamps are core to the domain, so the
    # framework runs JAX in x64 mode. The performance-critical paths
    # (sketches, hashing) still use explicit 32-bit dtypes — see
    # ops/hashing.py — so only the id/timestamp columns pay the TPU's
    # int64 emulation cost, and only on the query path.
    import jax as _jax

    _jax.config.update("jax_enable_x64", True)

__version__ = "0.4.0"

from zipkin_tpu.models.span import (  # noqa: F401
    Annotation,
    BinaryAnnotation,
    Endpoint,
    Span,
)
from zipkin_tpu.models.trace import Trace  # noqa: F401
from zipkin_tpu.models.dependencies import (  # noqa: F401
    Dependencies,
    DependencyLink,
    Moments,
)
