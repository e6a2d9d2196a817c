"""Online inference serving (docs/serving.md).

No reference equivalent — the reference stack stops at offline batch
inference (Inference.scala:27-79); this subsystem turns an exported
model into a low-latency online service on the existing cluster runtime
(engine supervision + manager IPC + checkpoint restore + telemetry),
see PARITY.md §2.2.

Pieces:
  - :mod:`~tensorflowonspark_tpu.serving.batcher` — dynamic
    micro-batching into padded power-of-two shape buckets;
  - :mod:`~tensorflowonspark_tpu.serving.replicas` — supervised model
    replicas with least-loaded dispatch and checkpoint hot-reload;
  - :mod:`~tensorflowonspark_tpu.serving.elastic` — degrade-by-resize
    replica pool: logical capacity, live param resharding on loss,
    adopt-on-respawn, graceful drain (docs/serving.md "Degrade by
    resize");
  - :mod:`~tensorflowonspark_tpu.serving.server` — in-process Client,
    stdlib HTTP endpoint, SLO stats, ``tfos-serve`` CLI;
  - :mod:`~tensorflowonspark_tpu.serving.decode` — continuous-batching
    autoregressive decode (block-paged KV cache, iteration-level
    scheduler, open-loop load generator).
"""

from tensorflowonspark_tpu.serving.batcher import (  # noqa: F401
    MicroBatcher,
    Overloaded,
    bucket_seq,
    bucket_size,
    pad_columns,
    pad_rows,
    pad_seq,
)
from tensorflowonspark_tpu.serving.decode import (  # noqa: F401
    DecodeEngine,
    DecodeSpec,
    PendingSession,
    run_open_loop,
)
from tensorflowonspark_tpu.serving.elastic import (  # noqa: F401
    ElasticReplicaPool,
)
from tensorflowonspark_tpu.serving.replicas import (  # noqa: F401
    ModelSpec,
    ReplicaPool,
)
from tensorflowonspark_tpu.serving.server import (  # noqa: F401
    Client,
    DecodeStats,
    Server,
    SLOStats,
    serve_http,
)
