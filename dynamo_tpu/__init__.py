"""dynamo_tpu — a TPU-native distributed LLM inference-serving framework.

Capabilities (modeled on NVIDIA Dynamo's feature set, re-designed TPU-first;
see SURVEY.md at the repo root for the structural map of the reference):

- OpenAI-compatible HTTP frontend with SSE streaming (`dynamo_tpu.frontend`)
- Lease-based service discovery + message fabric (`dynamo_tpu.runtime`)
- Content-addressed token blocks (`dynamo_tpu.tokens`)
- KV-cache-aware routing: radix prefix index + cost scheduler (`dynamo_tpu.router`)
- JAX/XLA/Pallas inference engine with paged KV cache and continuous
  batching over `jax.sharding.Mesh` (`dynamo_tpu.engine`, `dynamo_tpu.models`,
  `dynamo_tpu.ops`, `dynamo_tpu.parallel`)
- Disaggregated prefill/decode with KV transfer over ICI/DCN (`dynamo_tpu.disagg`)
- Multi-tier KV block manager HBM -> host DRAM -> disk (`dynamo_tpu.kvbm`)
- Load/SLA autoscaling planner (`dynamo_tpu.planner`)
"""

import time as _time

#: `time.perf_counter()` when the package was imported: the process's
#: start where the OS does not say it (`platform.process_age_s`)
IMPORTED_PERF_S = _time.perf_counter()

__version__ = "0.1.0"
