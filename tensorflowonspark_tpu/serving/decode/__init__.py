"""Continuous-batching autoregressive decode tier (docs/serving.md,
"Autoregressive decode").

No reference equivalent — the reference delegates all inference to TF
Serving (SURVEY.md §2.2); this package gives the framework an
in-framework LLM decode path on the existing serving runtime:

  - :mod:`~tensorflowonspark_tpu.serving.decode.kvcache` — the
    block-paged :class:`~.kvcache.PagedKVCache` (ref-counted prefix
    sharing through a prompt trie), the one cache of the engine and of
    a speculative draft model;
  - :mod:`~tensorflowonspark_tpu.serving.decode.scheduler` —
    iteration-level continuous batcher (mid-flight admission, one fused
    decode step per iteration, immediate slot retirement; prefix-hit
    admission, seeded sampling and draft-model speculative decoding
    ride the same loop);
  - :mod:`~tensorflowonspark_tpu.serving.decode.sampling` — seeded
    temperature/top-k/top-p sampling, pure in ``(logits, params,
    index)`` so failover replay and speculative verify are token-exact;
  - :mod:`~tensorflowonspark_tpu.serving.decode.loadgen` — open-loop
    Poisson load generator for TTFT / per-token SLOs, plus the
    shared-prefix traffic mix for the prefix-reuse bench lane.

The model half lives in ``models/transformer.py`` (``prefill``,
``prefill_extend``, ``decode_step_paged``,
``greedy_decode_reference``, reached through ``Config.decode_fns()``);
the frontend half in
``serving/server.py`` (``Server.generate``, ``POST /v1/generate``).
"""

from tensorflowonspark_tpu.serving.decode.loadgen import (  # noqa: F401
    run_open_loop,
    session_route_ids,
    shared_prefix_prompts,
)
from tensorflowonspark_tpu.serving.decode.sampling import (  # noqa: F401
    sample_token,
)
from tensorflowonspark_tpu.serving.decode.scheduler import (  # noqa: F401
    DecodeEngine,
    DecodeSpec,
    PendingSession,
)
