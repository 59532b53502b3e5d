"""Multi-replica serving: load balancing, load shedding, kill-safe
request migration.

One :class:`~quintnet_tpu.serve.engine.ServeEngine` is a single
continuous-batching process; this package runs N of them on worker
threads behind one submit/stream API and makes the resulting fleet
operable under the two things production traffic guarantees — bursts
and failures:

- :mod:`router`    — least-outstanding-work routing (token-count load
  proxy) or deterministic round_robin, with an adapter-affinity
  pre-filter for LoRA-bound requests (serve/adapters.py);
- :mod:`admission` — bounded fleet-wide queue; overload and expired
  deadlines shed with a typed :class:`Overloaded` instead of queueing
  forever;
- :mod:`health`    — per-replica circuit breaker (consecutive-failure
  trip, timed half-open probe) gating restarts of dead replicas;
- :mod:`replica`   — the ServeEngine worker thread: inbox, chaos
  polling (``ft.ChaosMonkey`` mode='raise'), and the death export of
  every unfinished request's host-side progress;
- :mod:`fleet`     — :class:`ServeFleet`: submit/result/generate,
  dispatcher, **exact migration** (a killed replica's in-flight
  requests resume on healthy replicas token-identically, via the same
  prompt+generated+key resume contract the engine's preemption path
  already guarantees), graceful drain, fleet metrics + per-replica
  compile-count enforcement.

Scaling past one address space (fleet/proc.py + fleet/frontdoor.py +
fleet/wire.py): :class:`ProcessFleet` runs each replica engine in its
OWN OS process behind the same submit/stream API — a length-prefixed
JSON wire protocol, heartbeat-supervised children restarted with
jittered backoff, and CRASH-SAFE migration from the dispatcher's
write-ahead token journal (a SIGKILL'd replica's in-flight requests
resume elsewhere token-identically with zero cooperation from the
corpse). :class:`FrontDoor` is the asyncio HTTP/SSE server in front of
either fleet, mapping the typed ``Overloaded`` shedding onto
429/503 + Retry-After.

Disaggregated serving (``ProcessFleet(pools={"prefill": P,
"decode": D})``, fleet/proc.py): the two regimes run on dedicated
replica pools — prefill replicas commit a request's first token and
ship its KV chain to a decode replica over a checksummed wire frame
(fleet/wire.py), retried under the shared
:class:`~quintnet_tpu.fleet.retry.RetryPolicy` with local re-prefill
as the always-correct fallback, and pool loss walks an explicit
degradation ladder surfaced at /healthz.

No benchmark cell goes through the fleet yet (PERF.md section 3): what
holds it is tests/test_fleet.py (threads, a mid-trace replica kill and
an over-capacity burst), tests/test_fleet_proc.py + test_fleet_wire.py
(processes) and tests/test_disagg.py (pools).
"""

from quintnet_tpu.fleet.admission import AdmissionQueue, Overloaded
from quintnet_tpu.fleet.fleet import FleetMetrics, FleetRequest, ServeFleet
from quintnet_tpu.fleet.frontdoor import FrontDoor
from quintnet_tpu.fleet.health import (CLOSED, DEAD, HALF_OPEN, HEALTHY,
                                       OPEN, STALLED, STARTING, STOPPED,
                                       Backoff, CircuitBreaker,
                                       HeartbeatMonitor)
from quintnet_tpu.fleet.proc import (POOLS, ProcessFleet, ProcReplica,
                                     replica_main)
from quintnet_tpu.fleet.replica import Replica
from quintnet_tpu.fleet.retry import RetryPolicy
from quintnet_tpu.fleet.router import ANY_POOL, POLICIES, Router, eligible

__all__ = [
    "AdmissionQueue",
    "Backoff",
    "CircuitBreaker",
    "FleetMetrics",
    "FleetRequest",
    "FrontDoor",
    "HeartbeatMonitor",
    "Overloaded",
    "ANY_POOL",
    "POLICIES",
    "POOLS",
    "ProcReplica",
    "ProcessFleet",
    "Replica",
    "RetryPolicy",
    "Router",
    "ServeFleet",
    "eligible",
    "replica_main",
    "HEALTHY",
    "DEAD",
    "STOPPED",
    "STARTING",
    "STALLED",
    "CLOSED",
    "OPEN",
    "HALF_OPEN",
]
