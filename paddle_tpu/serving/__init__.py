"""paddle_tpu.serving — online inference engine.

Dynamic micro-batching + shape buckets + AOT warmup over the
`inference.Predictor`: bounded request queue with typed backpressure
(`QueueFullError`), a batcher thread assembling micro-batches under a
`batch_timeout_ms` deadline, padding up a fixed `BucketLadder` so the
set of XLA signatures is bounded and precompilable (`warmup()`), and
full `observe` wiring (queue depth, batch size, padding waste,
queue/batch/compute latency). `Router` fronts a dynamic fleet of
engines as one endpoint (least-loaded + session-affinity placement,
failover, hedged requests under a retry budget, SLO-aware admission
via `observe.slo`); `FleetController` closes the loop over the SLO
signals (scale out/in, self-heal with exponential backoff, crash-loop
quarantine); per-request distributed tracing (`observe.reqtrace`)
follows each sampled request across the submit/batcher/dispatcher
threads under one trace id. `PhaseRouter` splits a decode fleet by
phase — prefill replicas (compute-bound, bucket-laddered) feeding
decode replicas (HBM-bound, paged) through the zero-copy KV handoff
in `serving.handoff`, with per-phase autoscaling policies
(`ttft_pressure` / `page_pressure`) plugging into `FleetController`.
`serving.tenancy` makes the fleet multi-tenant: priority classes +
token-bucket quotas charged at admission (`QuotaExceededError`),
priority-aware decode preemption/eviction, and a co-location policy
(`colocation_yield`) that pauses a background fine-tuning Trainer
under SLO pressure. See docs/serving.md; the chaos scenarios of the
fleet, the autoscaler, the disaggregated fleet and the multi-tenant
policies are tests/chaos.py, asserted in counts by tests/test_fleet.py,
test_autoscale.py, test_handoff.py and test_tenancy.py. A speed is
stated by benchmark/run.py alone.
"""

from .buckets import BatchInfo, BucketLadder, pow2_ladder  # noqa: F401
from .controller import (FleetController, ReplicaFactory,  # noqa: F401
                         page_pressure, ttft_pressure)
from .engine import (EngineClosedError, QueueFullError,  # noqa: F401
                     ServingEngine)
from .handoff import (HandoffError, KVDtypeMismatchError,  # noqa: F401
                      KVGeometryError, KVPacket)
from .router import (NoReplicaAvailableError, PhaseRouter,  # noqa: F401
                     Router, SLOShedError)
from .rpc import (ProcessReplicaFactory, RemoteCallError,  # noqa: F401
                  RemoteReplica, RemoteReplicaError, serve_engine)
from .tenancy import (PRIORITIES, QuotaExceededError,  # noqa: F401
                      Tenant, TenantRegistry, colocation_yield,
                      slo_burn_pressure, tenant_of_session)

# The decode subpackage (continuous batching + paged KV cache) imports
# lazily via `from paddle_tpu.serving import decode` /
# `from paddle_tpu.serving.decode import DecodeEngine`.
