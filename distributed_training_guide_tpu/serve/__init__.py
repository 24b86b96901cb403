"""Serving runtime: continuous-batching decode engine + paged KV cache,
grown into a distributed serving plane.

The training side of this repo ends at checkpoints; this package is the
inference side — iteration-level (Orca) scheduling over a block-table
paged (vLLM/PagedAttention) KV cache with a Pallas flash-decode kernel
(``ops/paged_decode.py``), refcounted copy-on-write prefix sharing,
optimistic admission with preemption-by-recompute, Sarathi-style chunked
prefill, a MESH-SHARDED page pool (``serve/sharding.py`` — pages split on
the kv-head axis under tp, attend shard_map'd over per-chip slices),
DISAGGREGATED prefill/decode engines connected by a refcounted page
handoff (``serve/disagg.py``, DistServe), a STREAMING request layer
(``serve/api.py`` — per-token SSE, deadlines, priorities, structured
refusals, lock-free metrics), SPECULATIVE DECODING
(``serve/spec.py`` — n-gram prompt-lookup and draft-model drafting with
exact-acceptance multi-token verification: spec-on output is
token-identical to spec-off at any temperature), and QUANTIZED KV PAGES
(``kv_dtype="int8"`` — block-wise absmax int8 payloads with
per-(position, kv-head) fp32 scales as first-class pool state,
dequantized in the flash kernel's tile loop: ~0.26-0.31x the fp32 pool
bytes, spec acceptance the built-in quality meter), and the
FAULT-TOLERANT MULTI-HOST FABRIC (``serve/router.py`` — prefix-affinity
+ least-loaded routing over N replicas with heartbeat fencing and
bitwise resubmission replay; ``serve/transport.py`` — the cross-host
branch of the page handoff: serialized k/v payloads over a CRC-framed
ack/commit wire whose only failure outcome is drop-free-requeue), now
ELASTIC at runtime (``serve/elastic.py`` — live engine-generation swaps:
grow/shrink ``n_slots``/page pool as a coordinated mass preemption that
seats or bitwise-replays every in-flight request; the router's replica
set is mutable via ``add_replica``/``remove_replica``/``swap_replica``),
with an OPEN-LOOP LOAD HARNESS (``serve/loadgen.py`` — Poisson/trace
arrivals over mixed scenario profiles, goodput + p50/p99 TTFT/ITL
tails, saturation sweeps) and an SLO-DRIVEN CONTROL PLANE
(``serve/controller.py`` — polls the lock-free stats snapshots and
actuates the elastic seams with hysteresis, cooldowns, drain-before-
remove scale-down, and an explicit degradation ladder).
See related-topics/serving/README.md.

One engine iteration (``ServeEngine.step``): expire deadlines, restore
from the host tier, admit, run one chunk budget of prefill, grow the
decoding slots, then one batched decode. A step that completes a prefill
on the plain path (K=1, no drafter) enqueues the chunk program and the
decode program back to back: the first token is sampled and seated in the
decode's token lanes on the device, the decode arrays go up while the
chunk program runs, and the host reads once the decode is enqueued, the
first token and then the decode's tokens.

    from distributed_training_guide_tpu.serve import (
        Request, ServeEngine, DisaggEngine, generate_many)
"""
from .engine import ModelPrograms, ServeEngine
from .kv_pages import PagePool, kv_page_bytes, pages_for_tokens
from .scheduler import (PrefixCache, RefusalError, Request, RequestResult,
                        Scheduler)

__all__ = [
    "Controller", "DisaggEngine", "Drafter", "DraftModelDrafter",
    "LoadReport", "ModelPrograms", "NgramDrafter", "PagePool",
    "PrefixCache", "RefusalError", "Replica", "Request", "RequestResult",
    "Router", "SLO", "Scenario", "Scheduler", "ServeEngine",
    "build_schedule", "default_scenarios", "generate_many",
    "kv_page_bytes", "local_fleet", "match_partition_rules",
    "new_generation", "pages_for_tokens", "poisson_arrivals",
    "prefix_affinity_key", "run_open_loop", "saturation_sweep",
    "serve_http", "spawn_like", "swap_engine", "swap_generation",
    "trace_arrivals",
]


def __getattr__(name):
    # generate_many / serve_http live in api.py (imports http.server),
    # DisaggEngine in disagg.py, the fleet router in router.py,
    # match_partition_rules in sharding.py, the spec drafters in
    # spec.py; keep the package import light for library users
    if name in ("generate_many", "serve_http", "throughput_stats"):
        from . import api

        return getattr(api, name)
    if name == "DisaggEngine":
        from .disagg import DisaggEngine

        return DisaggEngine
    if name in ("Replica", "Router", "local_fleet", "prefix_affinity_key"):
        from . import router

        return getattr(router, name)
    if name in ("Drafter", "DraftModelDrafter", "NgramDrafter"):
        from . import spec

        return getattr(spec, name)
    if name == "match_partition_rules":
        from .sharding import match_partition_rules

        return match_partition_rules
    if name in ("new_generation", "spawn_like", "swap_engine",
                "swap_generation"):
        from . import elastic

        return getattr(elastic, name)
    if name in ("LoadReport", "Scenario", "build_schedule",
                "default_scenarios", "poisson_arrivals", "run_open_loop",
                "saturation_sweep", "trace_arrivals"):
        from . import loadgen

        return getattr(loadgen, name)
    if name in ("Controller", "SLO"):
        from . import controller

        return getattr(controller, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
