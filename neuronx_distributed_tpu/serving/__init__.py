"""Continuous-batching serving subsystem (ISSUE 2 tentpole).

Iteration-level scheduling over the paged phase programs: requests enter
and leave the fixed-``B`` batch independently (per-slot KV offsets, block
tables over one page pool, chunked prefill), with per-request sampler params, rng streams, stop
conditions, streaming callbacks, FCFS admission control, cancellation and
deadlines — the serving layer the ROADMAP's "heavy traffic from millions of
users" north star points at.

- :mod:`.request` — Request/RequestOutput lifecycle (QUEUED → PREFILL →
  DECODE → {FINISHED, CANCELLED, TIMED_OUT}) and SamplingParams;
- :mod:`.scheduler` — the fixed-slot-table FCFS scheduler (pure host-side,
  property-tested: no slot leak, FIFO preserved, capacity bound);
- :mod:`.engine` — ``ServingEngine.step()``: sweep → admit/prefill →
  batched per-slot decode → stop detection → slot free, exporting telemetry
  through the PR-1 ``obs.MetricRegistry`` and ``serving_stats.jsonl``.

Hardened (resilience PR) against poisoned traffic and overload: non-finite
logits quarantine the one affected request (terminal ``FAILED`` state, slot
freed, co-batch untouched), ``max_queue`` bounds the admission backlog
(``BackpressureError``), ``step_timeout_s`` arms a step watchdog, and an
attached ``obs`` hub gives ``replay_trace`` a crash flight dump.

The KV cache (kvcache PR): ``ServingEngine(page_size=, num_pages=)`` keeps
K and V in the :mod:`~..kvcache` page pool, the engine's one KV
representation — :mod:`.paged`'s :class:`PagedKVManager` owns block tables,
page budgeting, prefix-cache reuse, and terminal-state reclamation.

Speculative decoding (spec PR): ``ServingEngine(draft=, spec_k=)`` turns
every decode step into a batched per-slot draft-k-verify
round — multi-token commit through one target verification forward,
rejected tails rolled back by page accounting, greedy output
token-identical to the plain engine, sampled output exactly distributed as
plain sampling via the residual-distribution correction, acceptance-rate
telemetry per request.

Fleet mode (fleet PR): :mod:`.fleet`'s :class:`FleetRouter` fronts N
``Replica``-wrapped engines with globally-unique request ids, pluggable
routing (round-robin / random / load-aware / prefix-affinity over a
host-side shadow of each replica's prefix chains) and zero-loss failover
(crash -> drain -> requeue on siblings -> warm restart).  :mod:`.driver`
is the shared Poisson drive loop — it takes an engine or a router.

Request-lifecycle tracing (tracing PR): ``ServingEngine(tracer=)`` /
``FleetRouter(tracer=)`` record one span tree per request — queue wait,
prefill chunks, decode steps, preemption gaps, failover hops — stitched
across replicas by the fleet-global id, exported as schema-checked
``trace_events.jsonl`` + Perfetto JSON (:mod:`~..obs.tracing`), and linked
from ``serving_stats`` v5 via ``trace_id``.  Zero overhead when off.

Stall-free SLO serving (SLO PR): ``ServingEngine(prefill_chunk_tokens=)``
sizes the page-aligned prefill chunks that interleave with decode steps
(Sarathi-style — long prompts stop stalling co-batched decodes,
token-identical whatever the width), ``Request.priority`` + deadlines turn the scheduler into a
two-tier EDF with slot preemption and bounded-wait anti-starvation, and
``shed_infeasible=True`` sheds dead-on-arrival deadlines at admission with
the distinct :class:`SLOInfeasible` signal.
"""

from neuronx_distributed_tpu.kvcache.allocator import PoolExhausted
from neuronx_distributed_tpu.serving.driver import (
    poisson_arrivals,
    replay,
    summarize_outputs,
)
from neuronx_distributed_tpu.serving.engine import (
    FAIL_NON_FINITE,
    SERVING_STATS_SCHEMA,
    ServingEngine,
    replay_trace,
)
from neuronx_distributed_tpu.serving.fleet import (
    FleetRouter,
    FleetUnavailableError,
    Replica,
    ReplicaState,
)
from neuronx_distributed_tpu.serving.paged import PagedKVManager
from neuronx_distributed_tpu.serving.request import (
    PRIORITIES,
    PRIORITY_BATCH,
    PRIORITY_INTERACTIVE,
    Request,
    RequestOutput,
    RequestState,
    SamplingParams,
)
from neuronx_distributed_tpu.serving.scheduler import (
    DEFAULT_MAX_BATCH_WAIT_S,
    AdmissionError,
    BackpressureError,
    RateLimited,
    SLOInfeasible,
    SlotScheduler,
    TokenBucket,
)

__all__ = [
    "ServingEngine",
    "SERVING_STATS_SCHEMA",
    "FAIL_NON_FINITE",
    "PagedKVManager",
    "PoolExhausted",
    "PRIORITIES",
    "PRIORITY_BATCH",
    "PRIORITY_INTERACTIVE",
    "Request",
    "RequestOutput",
    "RequestState",
    "SamplingParams",
    "AdmissionError",
    "BackpressureError",
    "RateLimited",
    "SLOInfeasible",
    "DEFAULT_MAX_BATCH_WAIT_S",
    "SlotScheduler",
    "TokenBucket",
    "replay_trace",
]
