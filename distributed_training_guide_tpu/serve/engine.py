"""Continuous-batching decode engine over the paged KV cache.

Compile surface (the whole point — requests come and go, programs don't):

- ONE batched decode program over the fixed ``[n_slots]`` slot array.
  Block tables / lengths / sampling knobs are int/float ARRAY arguments,
  idle slots compute into the trash page and are masked at the sample —
  admission, eviction, preemption, and page growth never retrace
  anything. EVERY paged attend — the decode step, the speculative
  verify forward, and the prefill chunk — defaults to the Pallas
  block-table kernel on TPU (``ops/paged_decode.py`` at query-tile
  block_q=T: O(live pages) reads per forward, no gathered view);
  ``attend_impl=`` selects the XLA gather reference explicitly, for all
  three forwards at once (one family per engine, never a mix).
- ONE prefill program, the chunk program: a prompt streams through the
  paged decode path ``prefill_chunk`` tokens at a time, each chunk
  attending over the already-committed pages, co-scheduled with resident
  decodes (Sarathi-style chunked prefill, Agrawal et al.
  arXiv:2308.16369) so a long prompt never stalls co-resident generation
  for its full length. The chunk budget bounds the extra decode latency
  per iteration. A model family therefore serves by exporting
  ``paged_decode_step`` alone (decode, chunk, verify and horizon programs
  all call it); ``pool_layout`` sizes its cache rows.
- One sampling program (temperature / top-k / top-p, per-slot scalars so
  co-resident requests can run different settings under one compile) and
  its batch-1 twin for prefill logits.

Between scheduler events (admission / eviction / preemption / growth) the
decode arrays live ON DEVICE: the decode program returns next-step tokens
and lengths alongside the samples, so a steady decode iteration transfers
one int32 per slot to the host (bookkeeping) and nothing back.

Sampling keys are ``fold_in(key(seed), absolute position of the sampled
token)`` — a pure function of (request seed, position), so a request's
tokens are identical whatever slot it lands in, whenever it is admitted,
whoever it shares the batch with, and whether or not it was preempted and
recomputed mid-flight. That property IS the order-invariance and
preemption-identity tests in tests/test_serve.py — and it is what makes
speculative decoding's acceptance EXACT here: the verification forward
(``verify_for`` — the same [S, T] multi-token form chunked prefill uses)
samples the target token at every drafted position from those same keys
and accepts a draft only when it matches, so spec-on emits literally the
spec-off stream, k+1 tokens per weight pass at best (serve/spec.py).

Sharded weights ride the existing ``parallel/plans.py`` meshes: pass
``plan=`` (tp / fsdp / single) and params are device_put to the plan's
param shardings. The KV page pool is replicated by default;
``shard_kv=True`` (tp meshes) splits it on the kv-head axis under the
``serve/sharding.py`` rules table and runs the attend — flash kernel
included — shard_map'd over per-chip pool slices, so no chip ever holds
the full-kv-head pool (ROADMAP item 2; HLO-pinned in tests).

The compiled programs live in :class:`ModelPrograms`, shared between this
monolithic engine and the disaggregated prefill/decode pair in
``serve/disagg.py`` (separate engines, same program cache, one page
pool).
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.registry import ModelBundle, family_module
from ..train.precision import Quantized
from ..utils.trace import (NOT_QUIET, STEP_ORDERS, install_gc_span, named,
                           span)
from .adapters import (AdapterPool, DEFAULT_TARGETS, ZERO_ADAPTER,
                       adapter_nbytes, adapter_pool_bytes, adapter_shapes,
                       init_adapter_stacks, validate_adapter_params)
from .kv_pages import (resolve_attend_for, copy_pages, init_pages,
                       kv_dtype_name, kv_page_bytes, make_attend,
                      sequence_state_layout, state_layout,
                       PagePool, pages_for_tokens, pool_nbytes, TRASH_PAGE,
                       window_layout, window_pages_bound)
from .scheduler import Admission, Request, RequestResult, Scheduler
from .spec import Drafter, NgramDrafter, new_spec_counters
from .tiering import (HostTier, cache_prefix_keys, make_gather,
                      restore_prefixes, restore_queued)
from .transport import gather_payload, scatter_payload
from .weights import (params_nbytes, quantized_param_shardings,
                      store_weights, weight_bytes_by_dtype,
                      weight_dtype_name)


@jax.named_scope("sample")
def _sample_tokens(logits, seeds, positions, temps, top_ks, top_ps):
    """Per-slot temperature / top-k / top-p sampling, greedy at temp 0.

    logits [S, V] fp32; all knobs are [S] arrays (per-slot scalars). The
    filters run in sorted space (one descending sort), the draw is
    categorical over the surviving set, and the sampled rank maps back to
    a vocab id through the sort order — no threshold/tie ambiguity.

    All-greedy batches skip the sampler entirely via a runtime cond: the
    vocab sort + threefry draw dominate a small decode step, and the
    greedy branch returns exactly the argmax that the temp<=0 lanes of
    the full branch would select — identical tokens, one branch executed.
    """
    s, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1)

    def _stochastic(logits, greedy, seeds, positions, temps, top_ks, top_ps):
        keys = jax.vmap(lambda sd, p: jax.random.fold_in(jax.random.key(sd), p))(
            seeds, positions)
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        order = jnp.argsort(-scaled, axis=-1)              # [S, V] vocab ids
        sorted_desc = jnp.take_along_axis(scaled, order, axis=-1)
        neg_inf = jnp.finfo(jnp.float32).min
        # top-k: keep ranks < k (k <= 0 disables)
        k_eff = jnp.where(top_ks > 0, top_ks, v).clip(1, v)
        ranks = jnp.broadcast_to(jnp.arange(v)[None, :], (s, v))
        kept = jnp.where(ranks < k_eff[:, None], sorted_desc, neg_inf)
        # top-p on the k-filtered distribution: keep the smallest prefix
        # whose cumulative prob reaches top_p (rank 0 always survives)
        probs = jax.nn.softmax(kept, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        kept = jnp.where(cum - probs < top_ps[:, None], kept, neg_inf)
        idx = jax.vmap(jax.random.categorical)(keys, kept)  # rank per slot
        sampled = jnp.take_along_axis(order, idx[:, None], axis=-1)[:, 0]
        return jnp.where(temps > 0, sampled, greedy)

    out = jax.lax.cond(
        jnp.any(temps > 0), _stochastic,
        lambda logits, greedy, *_: greedy,
        logits, greedy, seeds, positions, temps, top_ks, top_ps)
    return out.astype(jnp.int32)


def resolve_context_bounds(config, max_len: Optional[int],
                           page_size: int) -> tuple:
    """(capacity max_len, request-validation max_model_len, max_pages)
    for one engine — single-sourced so the monolith and the
    disaggregated facade can never disagree on sizing policy.

    Bounded default: the full position table of a big preset (131k for
    llama3) would size BOTH the default full-residency pool and the xla
    path's gather transient to the dense worst case this package exists
    to remove — long contexts are opt-in via max_len=. max_len is
    CAPACITY (page-granular); requests validate against min(capacity,
    position table) so a rounded-up capacity can't push gpt2 past its
    learned positions."""
    max_pos = getattr(config, "max_position_embeddings", None)
    if max_len is None:
        max_len = min(max_pos, 2048) if max_pos else 2048
    max_model_len = min(max_len, max_pos) if max_pos else max_len
    return max_len, max_model_len, pages_for_tokens(max_len, page_size)


def derived_pool_metrics(*, pool: PagePool, cached_pages: int, n_slots: int,
                         decode_steps: int, decode_tokens: int,
                         admitted: int, prefix_hits: int,
                         lat: "LatencyMeter",
                         bytes_per_page: int = 0,
                         pool_dtype: str = "fp32",
                         tier: Optional[HostTier] = None,
                         host_dispatches: int = 0,
                         horizon_ksum: int = 0) -> dict:
    """The derived stats() tail both engines expose (api.py's
    throughput_stats and /healthz index these keys on either).
    ``pages_cached_bytes`` sits next to the hit rate so cache pressure is
    visible in bytes, not just page counts — together with the
    scheduler's ``cache_evicted_pages`` counter a thrashing prefix cache
    (high hit rate, high churn) no longer looks healthy on /healthz.
    ``pool_dtype`` + ``bytes_per_page`` surface the quantization lever in
    bytes (scales included), so a kv_dtype="int8" capacity gain is a
    number on /healthz, not a vibe. The host-tier gauges
    (``host_tier_bytes`` / ``spilled_pages`` / ``restore_hits`` /
    ``restore_misses``) are always present — zeros without a tier — so
    /healthz and the router's fleet aggregation see one schema whether
    or not a replica spills."""
    held = pool.capacity - pool.n_free
    tier_tail = tier.gauges() if tier is not None else {
        "host_tier_bytes": 0, "host_tier_budget_bytes": 0,
        "spilled_pages": 0, "restore_hits": 0, "restore_misses": 0}
    return {
        **tier_tail,
        "n_slots": n_slots,
        "pool_dtype": pool_dtype,
        "bytes_per_page": bytes_per_page,
        "pages_capacity": pool.capacity,
        "pages_free": pool.n_free,
        "pages_held": held,
        "pages_cached": cached_pages,
        "pages_cached_bytes": cached_pages * bytes_per_page,
        "pool_occupancy": (round(held / pool.capacity, 3)
                           if pool.capacity else 0.0),
        "prefix_hit_rate": (round(prefix_hits / admitted, 3)
                            if admitted else 0.0),
        "decode_steps": decode_steps,
        "decode_tokens": decode_tokens,
        "decode_occupancy": (round(
            decode_tokens / (decode_steps * n_slots), 3)
            if decode_steps else 0.0),
        # dispatch amortization (the decode-horizon lever): one host
        # dispatch per decode at K=1, one per K fused device steps with a
        # horizon. ``horizon_ksum`` is the raw sum of realized horizon
        # lengths (summable fleet-wide — the router re-derives the means
        # from the sums); ``horizon_effective`` is the mean realized K
        # AFTER reservation shortening, so a pool too tight to ever grant
        # the requested horizon shows up as effective << requested
        "host_dispatches": host_dispatches,
        "horizon_ksum": horizon_ksum,
        "tokens_per_dispatch": (round(decode_tokens / host_dispatches, 3)
                                if host_dispatches else 0.0),
        "horizon_effective": (round(horizon_ksum / host_dispatches, 3)
                              if host_dispatches else 0.0),
        "ttft_s_avg": lat.ttft_avg(),
        "itl_s_avg": lat.itl_avg(),
    }


def spec_metrics(spec: dict, *, decode_steps: int, decode_tokens: int,
                 drafter: Optional[Drafter]) -> dict:
    """The speculation tail of stats(): drafted/accepted/rejected
    counters, the acceptance rate, and tokens-per-iteration (the
    weight-read amortization actually achieved — spec-off it is the
    decode occupancy in tokens, spec-on it can exceed the slot count).

    ``spec_acceptance_rate`` is OMITTED until something was drafted: a
    0.0 placeholder reads as "0% acceptance" on /healthz when the truth
    is "no speculation has run yet" — consumers use ``.get`` and treat
    the missing key as not-yet-measured."""
    drafted = spec["tokens_drafted"]
    out = {
        "spec_steps": spec["spec_steps"],
        "spec_tokens_drafted": drafted,
        "spec_tokens_accepted": spec["tokens_accepted"],
        "spec_tokens_rejected": spec["tokens_rejected"],
        "decode_tokens_per_step": (round(decode_tokens / decode_steps, 3)
                                   if decode_steps else 0.0),
    }
    if drafted:
        out["spec_acceptance_rate"] = round(
            spec["tokens_accepted"] / drafted, 3)
    if drafter is not None:
        out.update(drafter.stats())
    return out


def adapter_metrics(pool: Optional[AdapterPool], *,
                    publishes: int = 0) -> dict:
    """The multi-tenant tail of stats(): pool occupancy gauges plus
    insert/update/evict counters (LRU evictions split out — churn under
    pressure reads very differently from explicit retirement). Empty
    without a pool, so an adapter-free engine's stats() keys are exactly
    the pre-adapter set. The per-adapter request counts live in the
    scheduler's ``adapter_requests`` dict alongside this."""
    if pool is None:
        return {}
    return {
        "adapter_slots": pool.max_adapters,
        "adapter_capacity": pool.capacity,
        "adapters_live": pool.n_live,
        "adapters_free": pool.n_free,
        "adapter_occupancy": (round(pool.n_live / pool.capacity, 3)
                              if pool.capacity else 0.0),
        "adapter_inserts": pool.stats["inserts"],
        "adapter_updates": pool.stats["updates"],
        "adapter_evictions": pool.stats["evictions"],
        "adapter_lru_evictions": pool.stats["lru_evictions"],
        "adapter_publishes": publishes,
    }


def resolve_drafter(speculate, *, spec_k: int,
                    n_slots: Optional[int] = None) -> Optional[Drafter]:
    """The engines' ``speculate=`` knob: None/"off" disables, "ngram" is
    the built-in prompt-lookup drafter at depth ``spec_k``, and any
    :class:`~.spec.Drafter` instance (e.g. a configured
    ``DraftModelDrafter``) rides as-is (its own ``k`` wins). A drafter
    that carries per-slot state (``n_slots`` attribute) must cover the
    engine's slots — refusing here beats an IndexError deep inside
    ``propose_many`` on the first speculative iteration."""
    if speculate is None or speculate == "off":
        return None
    if speculate == "ngram":
        return NgramDrafter(k=spec_k)
    if isinstance(speculate, Drafter):
        drafter_slots = getattr(speculate, "n_slots", None)
        if (n_slots is not None and drafter_slots is not None
                and drafter_slots < n_slots):
            raise ValueError(
                f"drafter covers {drafter_slots} slots but the engine "
                f"decodes {n_slots} — build the drafter with n_slots >= "
                f"the engine's")
        return speculate
    raise ValueError(f"speculate must be None, 'off', 'ngram', or a "
                     f"Drafter instance, got {speculate!r}")


def collect_partial_tokens(scheds, handoffs=()) -> dict:
    """request_id -> tokens generated so far, for every LIVE sequence —
    THE streaming tap producer, single-sourced for the monolith and the
    disaggregated facade so the consumer contract lives in one place:
    lists only ever GROW (a post-preemption replay rewrites k/v, not
    tokens, and a speculative iteration appends its whole accepted run
    at once), so api.py's dedup-by-count slicing is exact and a spec
    iteration's accepted tokens all flush in that iteration's push."""
    out = {}
    for sched in scheds:
        for slot in sched.slots:
            if slot is not None and slot.generated:
                out[slot.request.request_id] = list(slot.generated)
    for h in handoffs:
        if h.generated:
            out[h.request.request_id] = list(h.generated)
    return out


# the engine's own chunk size, where the caller names none: the
# ``olmo2-7b-l12.serve.decode16`` deployment's (512 tokens a chunk step is
# what that cell's ITL carries beside 16 resident decodes), and never wider
# than one slot can hold
DEFAULT_PREFILL_CHUNK = 512


def resolve_prefill_chunk(prefill_chunk: Optional[int], *, max_pages: int,
                          page_size: int) -> int:
    """The chunk program's width T for one engine: the caller's number, or
    (None) the engine's own size — single-sourced so both engines, the CLI,
    ``models/sample.py`` and ``post/`` resolve it alike, and everything
    downstream (the ``serve_chunk_t<T>`` program, a generation swap, the
    reports) sees an integer."""
    if prefill_chunk is None:
        return min(DEFAULT_PREFILL_CHUNK, max_pages * page_size)
    if prefill_chunk < 1:
        raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
    return prefill_chunk


class LatencyMeter:
    """Running TTFT / inter-token-latency averages over finished
    requests (host-side counters feeding stats())."""

    def __init__(self):
        self.ttft_sum = self.itl_sum = 0.0
        self.ttft_n = self.itl_n = 0

    def note(self, finished: list) -> None:
        for res in finished:
            if res.first_token_at:
                self.ttft_sum += res.ttft_s
                self.ttft_n += 1
                if len(res.generated_ids) > 1:
                    self.itl_sum += res.itl_s
                    self.itl_n += 1

    def ttft_avg(self) -> float:
        return round(self.ttft_sum / self.ttft_n, 4) if self.ttft_n else 0.0

    def itl_avg(self) -> float:
        return round(self.itl_sum / self.itl_n, 6) if self.itl_n else 0.0


def run_fork(programs: "ModelPrograms", pages: dict, adm: Admission) -> None:
    """Device side of the CoW bookkeeping: the remainder prefill is about
    to write into the partially-shared page, so its content is copied
    into the slot's private replacement first. Mutates ``pages`` in
    place (the dict is the engine-shared handle)."""
    src, dst = adm.fork
    with span("serve.fork", request_id=adm.request.request_id):
        pages.update(programs._copy_fn(
            dict(pages), jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32)))


def advance_prefill_chunks(programs: "ModelPrograms", pages: dict,
                           sched: Scheduler, pending: dict, chunk: int,
                           on_complete) -> list:
    """Run up to ``chunk`` prompt tokens through the chunk program,
    oldest prefilling slot first — the per-iteration budget that bounds
    how much prompt work one iteration can absorb. ``on_complete(adm,
    logit)`` fires when a slot's final chunk lands (the engines differ
    there: the monolith samples the first token into the decode batch,
    the disaggregated prefill engine emits a Handoff); a non-None return
    is a finished RequestResult. The chunk program is only ENQUEUED here
    (``serve.prefill`` is its call, not its run): nothing in this function
    reads the device, so an ``on_complete`` that reads nothing either (the
    monolith's plain path, which leaves the first token on the device)
    hands the step on with the chunk still running, and the decode arrays
    and the decode program go up behind it.
    Single-sourced so budget discipline —
    charged at the padded PROGRAM cost, not real tokens (the PR-6 review
    fix) — cannot fork between the engines."""
    finished = []
    budget = chunk
    for slot_idx in sched.prefilling_indices():
        if budget <= 0:
            break
        adm = pending[slot_idx]
        slot = sched.slots[slot_idx]
        start = slot.cache_len
        real = min(chunk, slot.target_len - start)
        if sched.window is not None:   # the chunk's window-class pages
            sched.reserve_window(slot_idx, start, real)
        # budget is charged at the PROGRAM cost (the chunk is padded to
        # `chunk` whatever `real` is) — charging real tokens would let N
        # slots with short final chunks run N full-width forwards in one
        # iteration, exactly the latency spike the budget bounds
        budget -= chunk
        with span("serve.prefill", request_id=adm.request.request_id,
                  tokens=real, start=start, program=f"serve_chunk_t{chunk}"):
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :real] = adm.tokens[start:start + real]
            programs.prefill_calls += 1
            logit, pools = programs.chunk_for(chunk)(
                programs.params, dict(pages),
                jnp.asarray(ids), jnp.asarray([start], jnp.int32),
                jnp.asarray(sched.table_row(slot_idx)[None]),
                jnp.asarray(real - 1, jnp.int32),
                jnp.asarray([real], jnp.int32),
                *programs.lora_call_args([adm.request.adapter_id]))
            pages.update(pools)
            sched.commit_tokens(slot_idx, real)
        if not sched.slots[slot_idx].prefilling:   # final chunk landed
            pending.pop(slot_idx)
            res = on_complete(adm, logit)
            if res is not None:
                finished.append(res)
    return finished


def no_dev(reason: str) -> dict:
    """``_dev`` with nothing resident: ``kind`` None, and ``reason``, the
    event that took the arrays off the device (``utils/trace.py``'s
    ``REBUILD_REASONS``), which the next ``serve.build`` span reports. The
    cause travels in the handle itself, so ``run_decode_iteration`` and
    ``run_spec_decode`` need nothing of the engine that calls them."""
    return {"kind": None, "reason": reason}


class DecodeArrays:
    """What the monolith and the disaggregated decode engine share about
    ``_dev``, the decode arrays resident on the device between scheduler
    events. It is in one of three states: :func:`no_dev` (nothing resident,
    and why); a dict of the arrays under their program's ``kind`` (plain /
    spec / horizon) with ``stale`` None (the next decode uses them as they
    stand); or the same with ``stale`` the event that changed the block
    tables and nothing else (the next decode uploads ``tables`` alone).
    Every event that invalidates any of them goes through :meth:`drop_dev`
    or :meth:`stale_tables`, never an assignment."""

    _dev: dict

    def drop_dev(self, reason: str) -> None:
        """The next decode rebuilds every array from the scheduler: the set
        of decoding slots changed, or what a slot decodes under. The FIRST
        such cause since the last build is the one kept: a slot that left
        and whose successor was admitted and prefilled in the next iteration
        rebuilt because it ``left``. Tables that were stale go up with the
        whole set, and their cause (``grown``, ``lookahead``: the names of a
        table refresh alone) is not the build's: a write ahead reserved
        under a program in flight, a drain, and then a preemption rebuilds
        because a slot was ``preempted``."""
        if self._dev["kind"] is not None:
            self._dev = no_dev(reason)

    def stale_tables(self, reason: str) -> None:
        """Slots took pages and nothing else about the decoding set changed:
        the arrays stay on the device and the next decode uploads the block
        tables alone. Tokens and lengths have rolled forward there, and the
        sampling lanes belong to requests that did not change."""
        if self._dev["kind"] is not None and self._dev["stale"] is None:
            self._dev["stale"] = reason

    def reserve_ahead(self, sched: Scheduler, want: int) -> int:
        """``Scheduler.reserve_horizon`` for a program that will be enqueued
        behind host state the device has run ahead of: how many writes are
        covered for every decoding slot. Pages it gave are on no table of
        the device yet, whether or not the program goes up."""
        covered, grown = sched.reserve_horizon(want)
        if grown:
            self.stale_tables("lookahead")
        return covered


# what the verify program takes of ``Scheduler.decode_arrays()``: the
# candidate ids go up with every call, and it masks no lane by budget or eos
SPEC_ARRAYS = ("lengths", "tables", "seeds", "temps", "top_ks", "top_ps",
               "actives", "adapters")


def upload_decode_arrays(dev: dict, kind: str, sched: Scheduler, *,
                         placement, keys: Optional[tuple] = None,
                         lookahead: bool = False) -> dict:
    """Make ``dev`` what the ``kind`` program may be called with, and return
    it: the ONE place a decode program's inputs go up from, and the one that
    decides what goes. Nothing resident, or another program's set: the whole
    of ``sched.decode_arrays()`` (``keys`` of it). Resident with stale
    tables (:meth:`DecodeArrays.stale_tables`), or after the caller's own
    ``lookahead`` reservation: ``tables`` alone, one transfer. Otherwise
    nothing, and no span. The numpy work is ``serve.arrays`` and fills only
    what will go; the transfers are one ``jax.device_put`` an array
    (``serve.upload``, which says how many arrays and bytes); both lie
    inside ``serve.build``, whose ``reason`` is why ``dev`` could not be
    used as it stood. The arrays go to ``placement``
    (``ModelPrograms.operand_placement``) COMMITTED, as a program's own
    outputs are: a decode program then has ONE signature, whether a lane
    comes from the host, from the last step's outputs or from the one-lane
    token write of a chunk step, and the first call warms them all."""
    whole = dev["kind"] != kind
    if whole:
        reason = dev["reason"] if dev["kind"] is None else "kind"
    elif dev["stale"] is not None or lookahead:
        reason = dev["stale"] or "lookahead"
    else:
        return dev
    with span("serve.build", reason=reason):
        with span("serve.arrays"):
            if whole:
                arrays = sched.decode_arrays()
                if keys is not None:
                    arrays = {key: arrays[key] for key in keys}
            else:
                arrays = {"tables": sched.decode_tables()}
        with span("serve.upload") as up:
            out = {key: jax.device_put(v, placement)
                   for key, v in arrays.items()}
            up.set_metadata(arrays=len(arrays),
                            bytes=sum(v.nbytes for v in arrays.values()))
    return {**({} if whole else dev), "kind": kind, **out, "stale": None}


def run_spec_decode(programs: "ModelPrograms", pages: dict,
                    sched: Scheduler, drafter: Drafter, spec: dict,
                    dev: dict) -> tuple[list, int, dict]:
    """One SPECULATIVE decode iteration over the decoding slots, shared
    verbatim by the monolithic engine and the disaggregated decode
    engine (speculation semantics must never fork between them):

    1. host-side drafting — per-slot candidate streams from the drafter,
       each clipped to the request's remaining token budget and the
       engine's position table;
    2. opportunistic lookahead page growth (``ensure_lookahead`` — a
       slot that can't get its speculated positions' pages just drafts
       less, it never preempts anyone);
    3. ONE ``[S, k+1]`` verification forward through the paged cache
       (``verify_for`` — the chunked-prefill multi-token form), which
       scatters all candidate k/v and samples the TARGET token at every
       position with the plain decode path's fold_in(seed, position)
       keys;
    4. exact acceptance: a draft is accepted iff it equals the target's
       own draw, so the emitted run — accepted prefix plus the first
       disagreeing target draw — is literally the spec-off stream.
       Rejection rolls ``lengths`` back implicitly (``record_token``
       only ever advances by the emitted count, and the verify program
       returns the rolled-back lengths; the dead k/v past them is
       overwritten by the next scatter in place — no page churn).

    ``dev`` is the engine-managed device cache (``no_dev`` after an event
    that changed the decoding set, its tables marked stale after one that
    only gave slots pages, exactly like the plain path's ``_dev``): lengths
    roll forward ON DEVICE via the verify program's ``new_lengths`` output
    and the slow-changing arrays (tables, sampling knobs, actives) stay
    resident, so a steady spec iteration uploads only the [S, k+1] candidate
    ids + per-slot validity (and the tables, when a lookahead grew one) and
    reads back only (targets, n_acc) — the PR-6
    host-round-trip lesson, kept under speculation. The emitted tokens
    themselves come back in that read (the host needs them anyway for
    EOS checks and streaming).

    Returns (finished results, tokens emitted, updated dev cache) — or
    None when NO slot drafted anything this iteration: the padded
    [S, k+1] verify forward would then pay ~(k+1)x the projection/attend
    width to emit exactly one token per slot, so the caller runs the
    plain single-token program instead (lookup-hostile stretches cost
    spec-off speed, not a persistent slowdown).
    """
    active = sched.active_indices()
    k = int(drafter.k)
    t = k + 1
    contexts, budgets = {}, {}
    for i in active:
        slot = sched.slots[i]
        contexts[i] = list(slot.request.prompt_ids) + list(slot.generated)
        budgets[i] = max(0, min(
            k,
            # a draft past the request's own budget could never be
            # emitted (max emitted = remaining tokens)
            slot.request.max_new_tokens - len(slot.generated) - 1,
            # the verify scatter targets positions up to cache_len +
            # n_drafts, which must stay inside the position table
            sched.max_len - 1 - slot.cache_len))
    with span("serve.draft"):
        proposals = drafter.propose_many(contexts, budgets)
    if not any(proposals.get(i) and budgets[i] > 0 for i in active):
        return None
    ids = np.zeros((sched.n_slots, t), np.int32)
    n_valid = np.ones(sched.n_slots, np.int32)
    grew = False
    with span("serve.reserve"):
        for i in active:
            slot = sched.slots[i]
            ids[i, 0] = slot.generated[slot.replay_pos]
            props = [int(x) for x in (proposals.get(i) or [])][:budgets[i]]
            n_pages_before = len(slot.pages)
            granted = sched.ensure_lookahead(i, len(props))
            grew = grew or len(slot.pages) != n_pages_before
            props = props[:granted]
            ids[i, 1:1 + len(props)] = props
            n_valid[i] = 1 + len(props)
    # lookahead growth extended a block table since the last upload
    dev = upload_decode_arrays(dev, "spec", sched, keys=SPEC_ARRAYS,
                               placement=programs.operand_placement,
                               lookahead=grew)
    # static greedy specialization: when every active slot decodes at
    # temperature 0 the target draw is argmax and the verify program
    # skips the t-position sorted-space sampler entirely (exact — see
    # verify_for); a single stochastic slot switches the whole batch to
    # the full sampler program
    greedy = all(sched.slots[i].request.temperature == 0.0 for i in active)
    with span("serve.dispatch", program=f"serve_verify_t{t}"):
        targets, n_acc, dev["lengths"], pools = \
            programs.verify_for(t, greedy=greedy)(
                programs.params, dict(pages), jnp.asarray(ids),
                dev["lengths"], dev["tables"], dev["seeds"], dev["temps"],
                dev["top_ks"], dev["top_ps"], dev["actives"],
                jnp.asarray(n_valid),
                *programs.lora_call_args(dev["adapters"]))
        pages.update(pools)
    with span("serve.wait"):
        targets = np.asarray(targets)
        n_acc = np.asarray(n_acc)
    finished, emitted_total = [], 0
    with span("serve.book") as sp:
        for i in active:
            n_d = int(n_valid[i]) - 1
            acc = int(n_acc[i])
            spec["tokens_drafted"] += n_d
            spec["tokens_accepted"] += acc
            spec["tokens_rejected"] += n_d - acc
            for j in range(acc + 1):
                emitted_total += 1
                res = sched.record_token(i, int(targets[i, j]),
                                         from_decode=True)
                if res is not None:     # eos/length mid-run: the rest of
                    finished.append(res)   # the accepted tokens are dropped
                    break                  # with the slot (clean boundary)
        sp.set_metadata(tokens=emitted_total)
    spec["spec_steps"] += 1
    return finished, emitted_total, dev


def book_first_tokens(sched: Scheduler, first) -> tuple[list, set]:
    """Read the first tokens that ``ServeEngine._on_prefill_complete`` left
    on the device, ``(admission, token)`` each, and record them: each read
    is a ``serve.sample`` and waits for the chunk program that made the
    logit. Returns the requests their first token finished (eos) and those
    requests' slots."""
    finished, ended = [], set()
    for adm, token in first:
        with span("serve.sample", request_id=adm.request.request_id):
            token = int(token)
        res = sched.record_token(adm.slot_idx, token, from_decode=False)
        if res is not None:
            finished.append(res)
            ended.add(adm.slot_idx)
    return finished, ended


def live_lanes(sched: Scheduler) -> list:
    """``(slot, request id)`` of the decoding slots at a dispatch: what the
    booking of that program matches its lanes by, since the host may book it
    a step later, when a lane's request has left."""
    return [(i, sched.slots[i].request.request_id)
            for i in sched.active_indices()]


def dispatch_decode(programs: "ModelPrograms", pages: dict,
                    sched: Scheduler, dev: dict, *, seq: int,
                    first: list = (), ahead: bool = False) \
        -> tuple[list, dict]:
    """Enqueue the plain single-token program, no host synchronization, on
    the tokens and lengths the last one left on the device (or the host's,
    where ``upload_decode_arrays`` finds none resident); with ``ahead`` the
    program AFTER it behind it, on what it leaves in turn. Both lie under
    the step's ONE ``serve.dispatch`` (``programs`` says how many): a step
    has one such span and one ``serve.wait`` after it, whichever order it
    takes. ``first`` as ``run_decode_iteration`` has it.

    Returns the in-flight records :func:`book_inflight` consumes, one a
    program (the ``[n_slots]`` token future, a routing family's counters
    behind it; ``seq``, the step that enqueued it; the lanes live at the
    dispatch), and the updated dev cache."""
    dev = upload_decode_arrays(dev, "plain", sched,
                               placement=programs.operand_placement)
    # in front of the span, not inside it: ``serve.dispatch`` stays the
    # enqueue of the decode program, which the step's waterfall joins it with
    for adm, token in first:
        dev["tokens"] = programs._seat_fn(
            dev["tokens"], jnp.asarray(adm.slot_idx, jnp.int32), token)
    active = live_lanes(sched)
    records = []
    with span("serve.dispatch", program="serve_decode", programs=1 + ahead):
        for _ in range(1 + ahead):
            nxt, new_len, pools, *counted = programs._decode_fn(
                programs.params, dict(pages),
                dev["tokens"], dev["lengths"], dev["tables"], dev["seeds"],
                dev["temps"], dev["top_ks"], dev["top_ps"], dev["actives"],
                *programs.lora_call_args(dev["adapters"]))
            pages.update(pools)
            dev["tokens"], dev["lengths"] = nxt, new_len
            records.append({"kind": "plain", "k": 1, "seq": seq,
                            "block": counted[0] if counted else nxt,
                            "active": active})
    return records, dev


def run_decode_iteration(programs: "ModelPrograms", pages: dict,
                         sched: Scheduler, drafter: Optional[Drafter],
                         spec: dict, dev: dict, first: list = (), *,
                         seq: int = 0, ahead: bool = False) \
        -> tuple[list, int, dict, Optional[dict]]:
    """ONE decode iteration over the active slots, dispatched and read in
    the same step — the spec/plain
    dispatch, single-sourced for the monolith and the disaggregated
    decode engine (like ``run_spec_decode`` itself: neither the
    semantics NOR the scaffolding around them may fork between the two).
    Speculation runs when a drafter is configured, no active slot is
    replaying (a post-preemption replay must rewrite k/v through the
    SAME single-token program that wrote it — bitwise recompute, the
    PR-6 finding), and at least one slot actually drafted; otherwise the
    plain single-token program steps with its device-resident arrays.
    The two paths keep separate device caches keyed by ``kind`` —
    switching costs one upload of the whole set, what a slot joining or
    leaving costs; a slot that only grew a page costs either path its
    tables alone (``upload_decode_arrays``). The plain program runs on the
    tokens and lengths it left on the device itself: before every dispatch
    each resident array holds, for every active slot, what
    ``sched.decode_arrays()`` would upload (a replayed token the device
    sampled IS the recorded one, the bitwise-recompute rule above).

    ``first``: ``(admission, token)`` of the slots whose prefill completed
    in this step and whose first token is still ON THE DEVICE
    (``ServeEngine._on_prefill_complete``; the caller passes it only where
    the plain program will run: no drafter). Their ``tokens`` lanes went up
    as placeholders; each token is written into its lane there
    (``serve_seat_token``), the decode is dispatched behind the chunk
    program that is still running, and only then does the host read: the
    first tokens (``book_first_tokens``; they are ready when the chunk
    program ends, and are recorded at that instant, so a request's
    first-token time stays the moment the host had it), then the decode's
    (the step's ONE ``serve.wait``). A first token that ENDS its request
    (eos) means the decode ran one lane too many: that lane's token is not
    booked, and its write went to a page and a state block the slot owned
    and has freed, which no later program reads before it writes them.

    ``ahead`` (the caller's ``ServeEngine._ahead``; never with a drafter):
    the plain program for the token AFTER this one is enqueued behind this
    one before anything is read, and comes back as the record the next step
    books: the step enters the pipeline.

    Returns (finished, tokens emitted, dev, the record in flight or None).
    The caller owns the decode_steps/decode_tokens counters and must drop
    ``dev`` when a finished slot leaves the batch."""
    if drafter is not None and not any(sched.slots[i].replaying
                                       for i in sched.active_indices()):
        out = run_spec_decode(programs, pages, sched, drafter, spec, dev)
        if out is not None:
            return (*out, None)
    (record, *nxt), dev = dispatch_decode(programs, pages, sched, dev,
                                          seq=seq, first=first, ahead=ahead)
    finished, _ = book_first_tokens(sched, first)
    fin, emitted = book_inflight(programs, sched, record)
    return finished + fin, emitted, dev, (nxt[0] if nxt else None)


def dispatch_horizon(programs: "ModelPrograms", pages: dict,
                     sched: Scheduler, dev: dict, k: int, *,
                     seq: int = 0) -> tuple[dict, dict]:
    """Dispatch ONE fused K-step horizon — no host synchronization: jax's
    async dispatch returns futures, and the only blocking read is the
    ``np.asarray`` in :func:`book_inflight`, which the engine
    runs AFTER dispatching the next horizon (the double buffer: the
    device computes horizon h while the host books horizon h−1).

    The device-resident arrays of kind "horizon" are the plain decode set
    plus the per-slot live/budget/eos lanes the in-device masking consumes.
    They go up whole at a horizon boundary (``dev`` is then ``no_dev`` or
    another program's set; host and device state agree there). Between
    boundaries the block tables alone go up, where a reservation grew one
    since the last dispatch (``DecodeArrays.reserve_ahead``: they are
    host-owned) — while tokens/lengths/live/budgets stay device-resident
    (the previous horizon's outputs feed this one's inputs without
    readback). A
    slot that finished inside a still-unprocessed block is DEAD on device
    (its live lane went False in that block's scan), so its stale table
    row is masked to the trash page in-program and its freed pages may
    be re-issued to a later admission without corruption.

    Returns the in-flight record ``book_inflight`` consumes (the
    ``[n_slots, k]`` token-block future, the realized k, ``seq``, the step
    that enqueued it, and the (slot, request_id) pairs active at dispatch)
    and the updated dev cache."""
    dev = upload_decode_arrays(dev, "horizon", sched,
                               placement=programs.operand_placement)
    active = live_lanes(sched)
    with span("serve.dispatch", program=f"serve_horizon_k{k}"):
        (block, dev["tokens"], dev["lengths"], dev["actives"],
         dev["budgets"], pools) = programs.horizon_for(k)(
            programs.params, dict(pages),
            dev["tokens"], dev["lengths"], dev["tables"], dev["seeds"],
            dev["temps"], dev["top_ks"], dev["top_ps"], dev["actives"],
            dev["budgets"], dev["eos_ids"],
            *programs.lora_call_args(dev["adapters"]))
        pages.update(pools)
    return {"kind": "horizon", "k": k, "seq": seq, "block": block,
            "active": active}, dev


def book_inflight(programs: "ModelPrograms", sched: Scheduler,
                  inflight: dict) -> tuple[list, int]:
    """Read and book one dispatched decode program, plain or horizon: the
    ONE blocking device read of the step (``serve.wait``, whose
    ``waits_for`` is the step that enqueued the program: this step's own,
    or the one before in a pipelined step). Per lane, tokens record in
    order through ``record_token`` and stop at the first finish —
    record_token's eos-then-budget rule is exactly a horizon scan's
    live-mask update, so the host stops precisely where the device lane
    died (everything past it is masked zeros). A lane whose request has
    left its slot since the dispatch is skipped by request-id match: it
    finished in an EARLIER block or was evicted at a boundary (a horizon),
    or its first token or the token before this one was its eos (the plain
    program enqueued ahead ran ONE lane too many for ONE step: its write
    went to a page and a state block the slot owned and has freed, which no
    later program reads before it writes them; a state block's next owner
    starts from zeros at position 0). Returns (finished, tokens_emitted)."""
    with span("serve.wait", waits_for=inflight["seq"]):
        block = np.asarray(inflight["block"])
    if block.ndim == 1:     # the plain program's tokens, and behind them a
        if len(block) > sched.n_slots:      # routing family's counters
            programs.note_routing(block[sched.n_slots:])
        block = block[:sched.n_slots, None]
    finished, emitted = [], 0
    with span("serve.book") as sp:
        for slot_idx, rid in inflight["active"]:
            slot = sched.slots[slot_idx]
            if slot is None or slot.request.request_id != rid:
                continue
            for j in range(inflight["k"]):
                res = sched.record_token(slot_idx, int(block[slot_idx, j]),
                                         from_decode=True)
                emitted += 1
                if res is not None:
                    finished.append(res)
                    break
        sp.set_metadata(tokens=emitted)
    return finished, emitted


def drop_stale_pending(sched: Scheduler, pending: dict) -> None:
    """Preemption or deadline expiry may have evicted a mid-prefill
    slot; its chunk state must go with it (a preempted slot will be
    re-admitted from the queue)."""
    for idx in list(pending):
        slot = sched.slots[idx]
        adm = pending[idx]
        if (slot is None
                or slot.request.request_id != adm.request.request_id):
            del pending[idx]


def build_kv_report(programs: "ModelPrograms", *, page_size: int,
                    pool: PagePool, cached_pages: int, n_slots: int,
                    max_pages: int, pool_bytes: int,
                    tier: Optional[HostTier] = None,
                    decode_horizon: int = 1) -> dict:
    """The preflight-style byte table for one engine's pool. Priced at
    the pool's OWN kv_dtype (scale bytes included under int8), with the
    fp32 per-page cost alongside so the quantization gain is a ratio the
    reader can check against ``pool_bytes``. With a host tier attached
    the report grows its rows: budget, occupancy, resident spilled
    pages, and the page capacity the budget buys at this pool's
    per-page cost — the second storage tier in the same byte table."""
    kv_dtype = programs.kv_dtype
    per_page = kv_page_bytes(programs.config, page_size=page_size,
                             kv_dtype=kv_dtype)
    per_page_fp32 = kv_page_bytes(programs.config, page_size=page_size,
                                  kv_dtype="fp32")
    shards = (int(programs.mesh.shape["tp"]) if programs.shard_kv else 1)
    tier_rows = {} if tier is None else {
        "host_tier_budget_bytes": tier.budget_bytes,
        "host_tier_bytes": tier.bytes_used,
        "host_tier_spilled_pages": tier.spilled_pages,
        "host_tier_page_capacity": (tier.budget_bytes // per_page
                                    if per_page else 0),
    }
    return {
        **tier_rows,
        "page_size": page_size,
        "pool_dtype": kv_dtype,
        "n_pages": pool.n_pages,
        "pages_free": pool.n_free,
        "pages_cached": cached_pages,
        "bytes_per_page": per_page,
        "bytes_per_page_fp32": per_page_fp32,
        # a family with no attending layer: a page holds nothing
        "bytes_vs_fp32": (round(per_page / per_page_fp32, 4)
                          if per_page_fp32 else 0.0),
        "kv_shards": shards,
        "bytes_per_page_per_chip": per_page // shards,
        "pool_bytes": pool_bytes,
        "dense_equivalent_bytes": kv_page_bytes(
            programs.config, page_size=page_size,
            n_pages=n_slots * max_pages, kv_dtype=kv_dtype),
        # decode-horizon pricing: one host round-trip per K fused device
        # steps instead of per step, reading back a [n_slots, K] int32
        # block instead of [n_slots] — K× fewer dispatches for K× the
        # (tiny) readback payload
        "decode_horizon": decode_horizon,
        "horizon_block_bytes": n_slots * decode_horizon * 4,
        "dispatches_per_step": round(1 / decode_horizon, 4),
    }


def build_weight_report(programs: "ModelPrograms") -> dict:
    """The preflight-style byte table for one engine's WEIGHTS — the twin
    of :func:`build_kv_report`, priced at the params' own storage dtype
    (int8 scale bytes included) with the fp32 cost alongside so the
    quantization gain is a checkable ratio. ``publish_payload_bytes`` is
    what a quantized-layout publish (or an engine swap's param export)
    moves; ``publish_payload_bytes_fp`` is the fp-layout payload a trainer
    hands ``publish_params`` before the engine re-quantizes."""
    by_dtype = weight_bytes_by_dtype(programs._fp_layout,
                                     getattr(programs.bundle, "family", None))
    stored = params_nbytes(programs.params)
    fp_payload = sum(
        leaf.size * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(programs._fp_layout))
    return {
        "weight_dtype": programs.weight_dtype,
        "weight_bytes": stored,
        "weight_bytes_fp32": by_dtype["fp32"],
        "bytes_vs_fp32": round(stored / by_dtype["fp32"], 4),
        "weight_bytes_by_dtype": by_dtype,
        "publish_payload_bytes": stored,
        "publish_payload_bytes_fp": fp_payload,
    }


def build_adapter_report(programs: "ModelPrograms") -> dict:
    """The preflight-style byte table for one engine's ADAPTER pool —
    the third sibling of :func:`build_kv_report` /
    :func:`build_weight_report`. ``bytes_per_adapter`` is also the
    publish payload per insert: an adapter publish moves one slot's
    leaves, never the base weights — the consolidation lever this
    subsystem exists for."""
    pool = programs.adapter_pool
    if pool is None:
        return {}
    per = adapter_nbytes(programs.config, rank=pool.rank,
                         targets=pool.targets, bundle=programs.bundle)
    return {
        "max_adapters": pool.max_adapters,
        "rank": pool.rank,
        "targets": list(pool.targets),
        "bytes_per_adapter": per,
        "pool_bytes": pool.max_adapters * per,
        "publish_payload_bytes": per,
        "adapters_live": pool.n_live,
        "adapters_free": pool.n_free,
    }


def refuse_for_family(mod, family: str, asked: dict) -> None:
    """Options a family's serve path does not implement REFUSE here, at
    construction, by the option's name: a family lists them with the reason
    in ``SERVE_REFUSES`` (``models/mla.py``); none falls back quietly.
    ``asked`` maps each listed name to whether the caller asked for it."""
    refuses = getattr(mod, "SERVE_REFUSES", {})
    for option, wanted in asked.items():
        if wanted and option in refuses:
            raise ValueError(
                f"family {family!r} does not serve with {option}: "
                f"{refuses[option]}")


class ModelPrograms:
    """The compiled-program cache for one (model, params, sharding)
    triple: the batched decode step, the chunk program (the one prefill),
    the verify and horizon programs, the page-copy scatter, and the
    batch-1 sampler — every forward among them the family's
    ``paged_decode_step``. Owned
    by a :class:`ServeEngine`, or SHARED between the disaggregated
    prefill/decode pair (``serve/disagg.py``) — both engines then reuse
    one params layout and one jit cache.

    ``shard_kv=True`` is the distributed-pool mode: params follow the
    plan as usual, and every pool-touching program runs its pool work
    inside a full-manual shard_map with per-chip kv-head slices
    (``serve/sharding.py``).
    """

    def __init__(self, bundle: ModelBundle, params, *, plan=None,
                 shard_kv: bool = False, attend_impl: str = "auto",
                 kv_dtype=None, weight_dtype=None,
                 max_adapters: Optional[int] = None, adapter_rank: int = 8,
                 adapter_alpha: float = 16.0,
                 adapter_targets=DEFAULT_TARGETS):
        self.bundle = bundle
        self.config = bundle.config
        self.mod = family_module(bundle.family)
        refuse_for_family(self.mod, bundle.family, {
            "serving": True,    # a train-only family lists it (laguna)
            "kv_dtype='int8'": str(kv_dtype).lower() == "int8",
            "weight_dtype='int8'": str(weight_dtype).lower() == "int8",
            "max_adapters": max_adapters is not None,
            "plan / shard_kv": plan is not None or shard_kv})
        if not hasattr(self.mod, "paged_decode_step"):
            raise ValueError(
                f"family {bundle.family!r} does not serve: the serving "
                f"engine needs models/{bundle.family}.py to export "
                f"paged_decode_step (and pool_layout, where its cache rows "
                f"are not k/v heads)")
        if max_adapters is not None and not hasattr(self.mod, "_lora_sort"):
            raise ValueError(
                f"family {bundle.family!r} has no batched multi-LoRA "
                f"decode path — max_adapters needs the grouped-GEMM lora "
                f"hooks in models/llama.py")
        if attend_impl not in ("auto", "flash", "xla"):
            raise ValueError(f"attend_impl must be 'auto', 'flash' or "
                             f"'xla', got {attend_impl!r}")
        self.attend_impl = attend_impl
        # a routing family's counters, summed over its decode steps (the
        # decode program hands them over with its tokens): stats() reads it
        self.routing = {"steps": 0, "pairs_routed": 0, "pairs_held": 0,
                        "experts_touched": 0, "fullest_expert_pairs": 0}
        # the pool's storage dtype ("fp32" | "bf16" | "int8"; None inherits
        # the model dtype). int8 pools are Quantized pytrees — every
        # pool-touching program below threads them transparently, and the
        # scales are first-class pool state (CoW/commit/handoff/sharding)
        self.kv_dtype = kv_dtype_name(self.config, kv_dtype)
        # the PARAM storage dtype ("fp32" | "bf16" | "int8"; None inherits
        # the model's param dtype with NO transform — the pre-quantization
        # behavior, bit for bit). int8 params are Quantized pytrees
        # (serve/weights.py): int8 payload + per-block fp32 scales,
        # dequantized inside the matmul loops (ops/quantized_matmul.py),
        # never as a full fp32 tensor (the decode HLO pin).
        self.weight_dtype = weight_dtype_name(self.config, weight_dtype)
        # the fp layout is what trainers publish (post/loop.py merges in
        # fp); captured pre-transform so publish_params can accept either
        # layout and re-quantize through one compiled program
        self._fp_layout = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        if weight_dtype is not None:
            _wname, _wfam = self.weight_dtype, bundle.family
            self._store_weights = (
                lambda p: store_weights(p, _wname, family=_wfam))
        else:
            self._store_weights = None
        self.plan = plan
        self.shard_kv = bool(shard_kv)
        self.mesh = plan.mesh if plan is not None else None
        self._kv_sharding = None
        self._repl = None
        if self.shard_kv:
            from .sharding import (make_sharded_copy, serve_kv_shardings,
                                   validate_kv_shard)

            validate_kv_shard(plan, self.config)
            # the rules-table pattern: pool sharding comes from the serve
            # regex -> PartitionSpec table, not an ad-hoc spec here; the
            # probe mirrors the pool's pytree structure (payload + scales
            # under int8) so the sharding tree matches leaf for leaf
            leaf = np.zeros((2, 2, 2, 2, 2))
            if self.kv_dtype == "int8":
                leaf = Quantized(q=leaf.astype(np.int8),
                                 scale=np.zeros((2, 2, 2, 2, 1), np.float32))
            probe = {"pages": {"k": leaf, "v": leaf}}
            self._kv_sharding = serve_kv_shardings(
                self.mesh, probe)["pages"]["k"]
            self._repl = plan.replicated()
            copy_impl = make_sharded_copy(self.mesh)
        else:
            copy_impl = copy_pages
        if plan is not None:
            # shardings come from the FP layout (param_shardings' axes-tree
            # walk treats tuples as leaves, and Quantized IS a NamedTuple);
            # a storage transform then derives per-container shardings
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
            shardings = plan.param_shardings(
                bundle.param_logical_axes(self.config), shapes)
            if self._store_weights is not None:
                params = self._store_weights(params)
                shardings = quantized_param_shardings(shardings, params)
            params = jax.device_put(params, shardings)
        else:
            if self._store_weights is not None:
                params = self._store_weights(params)
            # canonical COMMITTED placement: params handed straight from
            # init/jit are uncommitted, and pjit keys its executable cache
            # on commitment — without this, the first publish_params
            # (whose device_put output is committed) would retrace every
            # program once, breaking the cache-flat-across-publishes pin
            params = jax.device_put(params, jax.devices()[0])
        self.params = params
        # where a program's small operands go (the decode arrays, the
        # adapter stacks): the params' own COMMITTED placement, replicated
        # over a plan's mesh
        self.operand_placement = (plan.replicated() if plan is not None
                                  else jax.devices()[0])

        # ---- pooled multi-LoRA adapters (serve/adapters.py) ----
        # the stacked A/B buffers are program ARGUMENTS (fixed avals, like
        # tables/lengths), so insert/evict/publish swap buffers without
        # touching any jit cache below; placement mirrors the params'
        # COMMITTED placement so the first insert can't retrace either
        self.adapter_pool: Optional[AdapterPool] = None
        self.adapter_stacks = None
        self._adapter_shapes = None
        self._insert_fn = None
        self.adapter_publish_count = 0
        if max_adapters is not None:
            self.adapter_pool = AdapterPool(
                max_adapters, rank=adapter_rank, alpha=adapter_alpha,
                targets=adapter_targets)
            self._adapter_shapes = adapter_shapes(
                self.config, rank=adapter_rank, targets=adapter_targets,
                bundle=bundle)
            stacks = init_adapter_stacks(
                self.config, max_adapters=max_adapters, rank=adapter_rank,
                targets=adapter_targets, bundle=bundle)
            self.adapter_stacks = jax.device_put(stacks,
                                                 self.operand_placement)
            # ONE compiled insert for every slot: the slot index is a
            # TRACED scalar, so publishing into slot 3 and slot 7 hit the
            # same executable (jit-cache-flat across inserts)
            self._insert_fn = jax.jit(named(self._adapter_insert,
                                            "serve_adapter_insert"))

        kv_out = self._pool_shardings() if self.shard_kv else None
        self._chunk_fns = {}
        self._verify_fns = {}
        self._horizon_fns = {}
        self._copy_fn = jax.jit(named(copy_impl, "serve_copy"),
                                donate_argnums=(0,),
                                **({"out_shardings": kv_out}
                                   if kv_out else {}))
        self._decode_fn = jax.jit(
            named(self._decode, "serve_decode"), donate_argnums=(1,),
            **({"out_shardings": (self._repl, self._repl, kv_out)}
               if self.shard_kv else {}))
        self._sample_one = jax.jit(named(
            lambda logit, seed, pos, t, tk, tp: _sample_tokens(
                logit[None], seed[None], pos[None], t[None], tk[None],
                tp[None])[0], "serve_sample_one"))
        # a first token into its lane of the decode's ``tokens`` where both
        # lie on the device (``run_decode_iteration``). The slot is an
        # OPERAND: one executable for every slot, compiled by the first
        # chunk step an engine runs
        self._seat_fn = jax.jit(named(
            lambda tokens, slot, token: tokens.at[slot].set(token),
            "serve_seat_token"))
        # weight-publish bookkeeping (post-training: post/loop.py). A
        # publish swaps refreshed buffers into self.params WITHOUT touching
        # the jit caches above — the programs take params as an argument,
        # so identical avals mean zero retraces (jit_cache_sizes pins it).
        self.publish_count = 0
        self._swap_in_flight = False
        self._snapshot_fn = None
        self._requant_fn = None
        # prefill FORWARD count (one per chunk program call) — the
        # zero-prefill pin for tier restores and fleet
        # directory pulls: a restored/pulled context must seat without
        # moving this counter beyond what its warm-cache control moves it
        self.prefill_calls = 0
        # host tier for ADAPTER spills (serve/tiering.py): attached by
        # the owning engine — with shared programs the LAST attached
        # tier hosts the pool's spills (the AdapterPool is fleet-shared
        # there anyway)
        self._host_tier = None

    # ---- weight publishing (the post-training seam) ------------------------
    @contextlib.contextmanager
    def swap_guard(self):
        """Marks an engine-generation swap in flight on this program cache
        (``serve/elastic.py swap_generation`` holds it for the whole
        export/seat window). ``publish_params`` refuses while it is held:
        the swap replays preempted sequences bitwise through these
        programs, and a weight publish landing mid-swap would make the
        replayed tokens diverge from the recorded ones — silent stream
        corruption, the one outcome the swap protocol exists to prevent."""
        if self._swap_in_flight:
            raise RuntimeError("an engine generation swap is already in "
                               "flight on this ModelPrograms")
        self._swap_in_flight = True
        try:
            yield self
        finally:
            self._swap_in_flight = False

    def publish_params(self, new_params) -> int:
        """Swap refreshed parameters into every compiled program — the
        trainer->engine seam of the post-training loop (post/loop.py).

        The decode/prefill/verify programs take params as an ARGUMENT, so
        a publish is a buffer rebind, not a program change: as long as the
        incoming pytree matches the compiled layout exactly (treedef,
        per-leaf shape and dtype), every jit cache hits and the next
        decode step runs the already-compiled executable over the new
        weights — retrace-free by design, pinned by ``jit_cache_sizes``
        staying flat across publishes and by decode-after-publish being
        bitwise equal to a fresh engine built from the published params.

        A mismatched pytree fails LOUDLY naming the offending leaf
        (a stale-layout publish reaching the embedding gather would
        produce garbage tokens with a 200, not an error), and a publish
        is rejected outright while a generation swap is in flight (see
        ``swap_guard``). Host arrays are accepted: leaves are placed onto
        the compiled layout's shardings (the plan's param placement, or
        default device placement for single-device engines). The
        incoming leaves are COPIED, never donated — the caller keeps its
        tree (a non-shared fleet publishes one tree into several caches),
        and see the snapshot comment below for why donation is banned on
        this jaxlib.

        Returns the new publish count."""
        if self._swap_in_flight:
            raise RuntimeError(
                "cannot publish params while an engine generation swap is "
                "in flight: the swap replays in-flight sequences bitwise "
                "through these programs, and new weights mid-swap would "
                "corrupt every replayed stream — publish before the swap "
                "or after it completes")
        old_flat, old_def = jax.tree_util.tree_flatten(self.params)
        new_flat, new_def = jax.tree_util.tree_flatten(new_params)
        if old_def != new_def:
            # a weight-transformed engine (weight_dtype=) also accepts the
            # FP layout the trainer naturally produces, re-quantizing it
            # through one compiled program on the validated path below
            if (self._store_weights is not None and new_def
                    == jax.tree_util.tree_structure(self._fp_layout)):
                return self._publish_fp(new_params)
            raise ValueError(
                f"published params tree does not match the compiled "
                f"layout: got {new_def}, compiled {old_def} — a "
                f"stale-layout publish would produce garbage tokens, not "
                f"an error, so it is refused here")
        old_paths = jax.tree_util.tree_flatten_with_path(self.params)[0]
        for (path, old_leaf), new_leaf in zip(old_paths, new_flat):
            name = jax.tree_util.keystr(path)
            new_shape = tuple(getattr(new_leaf, "shape", ()))
            new_dtype = np.asarray(new_leaf).dtype \
                if not hasattr(new_leaf, "dtype") else new_leaf.dtype
            if new_shape != tuple(old_leaf.shape):
                raise ValueError(
                    f"published leaf {name} has shape {new_shape} but the "
                    f"compiled layout expects {tuple(old_leaf.shape)}")
            if jnp.dtype(new_dtype) != jnp.dtype(old_leaf.dtype):
                raise ValueError(
                    f"published leaf {name} has dtype {new_dtype} but the "
                    f"compiled layout expects {old_leaf.dtype}")
        # SNAPSHOT onto the compiled layout's shardings: the engine OWNS
        # its buffers. A bare device_put would alias identically-placed
        # incoming leaves — and the post-training trainer DONATES its
        # state into the next update step, which would delete the
        # engine's params out from under the decode ("buffer has been
        # deleted or donated" mid-rollout, found the hard way when a
        # guard-skipped publish deferred the rebinding). One compiled
        # copy program, built on first publish, reused forever — the old
        # leaves drop their last reference when self.params rebinds.
        if self._snapshot_fn is None:
            shardings = jax.tree.map(lambda leaf: leaf.sharding,
                                     self.params)
            # ALWAYS copy, never donate: a donate_argnums twin (reusing
            # the loop's merge-output buffers — one fewer params copy
            # per publish) segfaulted this container's jaxlib inside a
            # later persistent-cache executable deserialization, the
            # ROADMAP caveat-(c) glibc-heap corruption in a new coat.
            # Re-try the donating twin when jaxlib is upgraded.
            self._snapshot_fn = jax.jit(
                named(lambda p: jax.tree.map(jnp.copy, p), "serve_snapshot"),
                out_shardings=shardings)
        self.params = self._snapshot_fn(new_params)
        self.publish_count += 1
        return self.publish_count

    def _publish_fp(self, new_params) -> int:
        """FP-layout publish into a weight-transformed engine: validate
        against the captured fp layout (same loud per-leaf contract as the
        compiled-layout path), then quantize/cast + copy under ONE compiled
        program pinned to the compiled layout's shardings. Built once on
        first fp publish, reused forever — the serving programs never see a
        new aval, so every jit cache stays flat (the retrace-free pin).
        The trailing tree.map(jnp.copy) exists for the leaves the storage
        transform passes through untouched (norm scales, biases): without
        it the jit would alias the trainer's buffers, which the trainer
        then donates into its next update step (see the snapshot comment
        above — same hazard, same cure, never donate)."""
        fp_paths = jax.tree_util.tree_flatten_with_path(self._fp_layout)[0]
        new_flat = jax.tree_util.tree_leaves(new_params)
        for (path, fp_leaf), new_leaf in zip(fp_paths, new_flat):
            name = jax.tree_util.keystr(path)
            new_shape = tuple(getattr(new_leaf, "shape", ()))
            new_dtype = np.asarray(new_leaf).dtype \
                if not hasattr(new_leaf, "dtype") else new_leaf.dtype
            if new_shape != tuple(fp_leaf.shape):
                raise ValueError(
                    f"published leaf {name} has shape {new_shape} but the "
                    f"fp publish layout expects {tuple(fp_leaf.shape)}")
            if jnp.dtype(new_dtype) != jnp.dtype(fp_leaf.dtype):
                raise ValueError(
                    f"published leaf {name} has dtype {new_dtype} but the "
                    f"fp publish layout expects {fp_leaf.dtype}")
        if self._requant_fn is None:
            shardings = jax.tree.map(lambda leaf: leaf.sharding,
                                     self.params)
            store = self._store_weights
            self._requant_fn = jax.jit(
                named(lambda p: jax.tree.map(jnp.copy, store(p)),
                      "serve_requant"),
                out_shardings=shardings)
        self.params = self._requant_fn(new_params)
        self.publish_count += 1
        return self.publish_count

    # ---- adapter publishing (the multi-tenant seam) ------------------------
    def _adapter_insert(self, stacks, payload, slot):
        """One adapter's leaves into the stacked pool at a TRACED slot —
        ``dynamic_update_slice`` on the adapter axis (axis 1, after the
        leading layer axis), fp32 like the stacks. Copies, never donates
        (the publish-snapshot discipline: the caller keeps its tree)."""
        out = {}
        for t, pair in stacks.items():
            upd = {}
            for leaf in ("a", "b"):
                buf = pair[leaf]
                new = jnp.expand_dims(
                    payload[t][leaf].astype(buf.dtype), 1)
                start = (0, slot) + (0,) * (buf.ndim - 2)
                upd[leaf] = jax.lax.dynamic_update_slice(buf, new, start)
            out[t] = upd
        return out

    def publish_adapter(self, adapter_params, *, name: Optional[str] = None,
                        slot: Optional[int] = None) -> int:
        """Insert (or republish) ONE tenant adapter into the stacked pool
        — ``publish_params``' little sibling: validated per leaf against
        the pool's (rank, targets) geometry, refused while a generation
        swap is in flight, and retrace-free by construction (the stacks
        are program arguments; the insert runs one compiled
        ``dynamic_update_slice`` whatever the slot). ``slot=None`` claims
        a slot (LRU-evicting an idle adapter under pressure); a concrete
        ``slot`` republishes a live tenant in place (continual tuning).
        The payload is ``models/lora.py``'s ``params['lora']`` layout —
        a trained adapter publishes without reshaping. Returns the slot
        id requests should carry as ``adapter_id``."""
        if self.adapter_pool is None:
            raise ValueError(
                "this engine serves no adapter pool (built with "
                "max_adapters=None) — adapters cannot be published into "
                "it; rebuild with max_adapters=")
        if self._swap_in_flight:
            raise RuntimeError(
                "cannot publish an adapter while an engine generation "
                "swap is in flight: the swap replays in-flight sequences "
                "bitwise through these programs — publish before the "
                "swap or after it completes")
        validate_adapter_params(self._adapter_shapes, adapter_params)
        pool = self.adapter_pool
        if slot is None:
            slot = pool.alloc(name)
            if slot is None:
                raise RuntimeError(
                    f"adapter pool exhausted: all {pool.capacity} tenant "
                    f"slots are live with in-flight requests — drain a "
                    f"tenant or build the engine with a larger "
                    f"max_adapters")
        else:
            if slot == ZERO_ADAPTER:
                raise ValueError("adapter slot 0 is the zero adapter and "
                                 "is never published into")
            if not pool.is_live(int(slot)):
                raise ValueError(
                    f"adapter slot {slot} is not live — omit slot= to "
                    f"allocate one, or publish into a live slot "
                    f"({pool.live_slots()}) to refresh that tenant")
            slot = int(slot)
            pool.mark_update(slot)
        self.adapter_stacks = self._insert_fn(
            self.adapter_stacks, adapter_params,
            jnp.asarray(slot, jnp.int32))
        self.adapter_publish_count += 1
        return slot

    def attach_host_tier(self, tier) -> None:
        """Install the host tier on the ADAPTER eviction path: an
        AdapterPool LRU eviction (a new insert past ``max_adapters``
        recycling an idle tenant's slot) serializes the victim's A/B
        leaves into the tier instead of discarding them, and
        ``restore_adapter`` re-inserts on next reference — no fleet
        republish of weights the host already held."""
        self._host_tier = tier
        if self.adapter_pool is not None:
            self.adapter_pool.on_evict = self._spill_adapter

    def _spill_adapter(self, slot: int, name) -> None:
        """AdapterPool ``on_evict`` hook: gather the victim slot's rows
        (fp32, bitwise) BEFORE the incoming insert overwrites them."""
        if self._host_tier is None or self.adapter_stacks is None:
            return
        payload = {f"{t}.{leaf}": np.asarray(pair[leaf][:, slot])
                   for t, pair in self.adapter_stacks.items()
                   for leaf in ("a", "b")}
        self._host_tier.put(("adapter", name), payload, pages=0,
                            meta={"slot": int(slot)})

    def restore_adapter(self, name) -> Optional[int]:
        """Re-insert a spilled tenant from the host tier into a (possibly
        newly LRU-recycled) slot, through the same compiled insert as a
        publish — the stacks rows land bitwise what the spill gathered.
        Returns the new slot id, or None when the tier holds no record
        for ``name`` (or allocation is impossible: every slot live with
        in-flight requests)."""
        if self.adapter_pool is None or self._host_tier is None:
            return None
        # peek-and-hold BEFORE alloc: the alloc below may LRU-evict some
        # other tenant, whose cascade spill could push THIS record out of
        # the byte budget — the held reference keeps the payload alive
        rec = self._host_tier.get(("adapter", name))
        if rec is None:
            return None
        slot = self.adapter_pool.alloc(name)
        if slot is None:
            return None
        self._host_tier.take(("adapter", name))
        payload = {t: {leaf: jnp.asarray(rec.payload[f"{t}.{leaf}"])
                       for leaf in ("a", "b")}
                   for t in self.adapter_stacks}
        self.adapter_stacks = self._insert_fn(
            self.adapter_stacks, payload, jnp.asarray(slot, jnp.int32))
        return slot

    def jit_cache_sizes(self) -> dict:
        """Per-program jit cache sizes — the retrace meter. A weight
        publish must leave every number here unchanged (the acceptance
        pin of the post-training loop: a policy update is a
        weight-publish, not a recompile)."""
        sizes = {
            "decode": self._decode_fn._cache_size(),
            "copy": self._copy_fn._cache_size(),
            "sample_one": self._sample_one._cache_size(),
            "seat_token": self._seat_fn._cache_size(),
        }
        if self._insert_fn is not None:
            sizes["adapter_insert"] = self._insert_fn._cache_size()
        for t, fn in self._chunk_fns.items():
            sizes[f"chunk_{t}"] = fn._cache_size()
        for key, fn in self._verify_fns.items():
            sizes[f"verify_{key}"] = fn._cache_size()
        for k, fn in self._horizon_fns.items():
            sizes[f"horizon_{k}"] = fn._cache_size()
        return sizes

    # ---- state placement ---------------------------------------------------
    def init_device_pages(self, n_pages: int, page_size: int,
                          n_window_pages: Optional[int] = None,
                          n_state_blocks: Optional[int] = None) -> dict:
        """Zeroed pools placed per the serve sharding rules (kv-head
        split under shard_kv, replicated under a plain plan)."""
        pages = init_pages(self.config, n_pages, page_size,
                           kv_dtype=self.kv_dtype,
                           n_window_pages=n_window_pages,
                           n_state_blocks=n_state_blocks)
        if self.shard_kv:
            return jax.device_put(pages, self._pool_shardings())
        if self.plan is not None:
            return jax.device_put(pages, self.plan.replicated())
        return pages

    def _pool_shardings(self) -> dict:
        """The sharded pool's placement, leaf by leaf (k and v: the mesh
        paths refuse a family with a third leaf)."""
        return {"k": self._kv_sharding, "v": self._kv_sharding}

    def make_attend(self, tables, lengths, *, impl: Optional[str] = None,
                    n_valid=None):
        """The attend callback every layer calls with the stacked pools and
        its index (``kv_pages.paged_attend``'s contract) — shard_map'd
        per-chip pool slices under shard_kv, the plain callback otherwise."""
        impl = self.attend_impl if impl is None else impl
        if self.shard_kv:
            from .sharding import make_sharded_attend

            return make_sharded_attend(self.mesh, tables, lengths,
                                       impl=impl, n_valid=n_valid)
        # a state class's block ids are the tables' last column
        return make_attend(
            tables, lengths, impl=impl, n_valid=n_valid,
            state_class=sequence_state_layout(self.config) is not None)

    # ---- compiled programs -------------------------------------------------
    def _lora_ctx(self, lora_args) -> Optional[dict]:
        """The ``lora=`` dict the model forwards take, from the optional
        trailing ``(stacks, adapters)`` program arguments — None when the
        engine serves no adapter pool, and the programs then trace
        exactly the pre-adapter graph (byte-identical compile surface)."""
        if not lora_args:
            return None
        stacks, adapters = lora_args
        return {"scale": self.adapter_pool.scale, "adapters": adapters,
                "stacks": stacks, "impl": "auto"}

    def lora_call_args(self, adapters) -> tuple:
        """Trailing program arguments for one forward: ``()`` without a
        pool, else ``(stacks, adapters[int32])`` — both ARRAYS, so any
        adapter mix and any pool content run the one compiled program."""
        if self.adapter_pool is None:
            return ()
        return (self.adapter_stacks, jnp.asarray(adapters, jnp.int32))

    def _decode(self, params, pools, tokens, lengths, tables, seeds, temps,
                top_ks, top_ps, actives, *lora_args):
        attend = self.make_attend(tables, lengths)
        logits, cache = self.mod.paged_decode_step(
            self.config, params, tokens[:, None], lengths, pools, attend,
            **({"lora": self._lora_ctx(lora_args)} if lora_args else {}))
        nxt = _sample_tokens(logits.astype(jnp.float32), seeds, lengths + 1,
                             temps, top_ks, top_ps)
        nxt = jnp.where(actives, nxt, 0)
        # the returned (tokens, lengths) ARE next step's inputs: a steady
        # decode run round-trips nothing but the sampled ids to the host
        routing = cache.pop("routing", None)
        out = (nxt, jnp.where(actives, lengths + 1, lengths), cache)
        if routing is not None:   # a routing family's counters ride the
            # host's one read of the step, behind the sampled ids
            out += (jnp.concatenate([nxt, routing]),)
        return out

    def note_routing(self, counts) -> None:
        """One decode step's ``[pairs routed, pairs held here, experts
        touched, fullest expert's pairs]`` (``models/mla.py``)."""
        r = self.routing
        r["steps"] += 1
        r["pairs_routed"] += int(counts[0])
        r["pairs_held"] += int(counts[1])
        r["experts_touched"] += int(counts[2])
        r["fullest_expert_pairs"] = max(r["fullest_expert_pairs"],
                                        int(counts[3]))

    def horizon_for(self, k: int):
        """The fused K-step decode program (``decode_horizon=K``): ONE
        compiled ``lax.scan`` of K decode iterations, so a steady decode
        pays one host dispatch — and one ``[n_slots, K]`` int32 readback
        — per K tokens per slot instead of per token.

        Each scan step IS ``_decode`` with the live mask threaded
        through: a lane goes dead mid-horizon exactly where the host's
        ``record_token`` would finish it (EOS first — ``eos_ids >= 0``
        guards the no-eos case — then budget exhaustion), after which
        its block table masks to the trash page (its scatters AND
        attends route to page 0), its emitted tokens mask to 0, and its
        length/budget freeze. Sampling keys are position-keyed
        (``fold_in(seed, absolute position)``), so the K-step stream is
        token-identical to K single steps BY CONSTRUCTION — the horizon
        changes when the host observes tokens, never which tokens exist.

        The scan carries the kv pools; the per-step stacked output is
        only the ``[K, n_slots]`` token block — the cache avals stay
        pool-shaped in and out (the HLO pin tests/test_multistep.py
        checks), so fusing K steps costs zero extra pool memory.

        Returns ``(block [n_slots, K], tokens, lengths, live, budgets,
        k_pages, v_pages)`` — everything after the block is next
        horizon's device-resident input."""
        if k < 1:
            raise ValueError(f"decode horizon must be >= 1, got {k}")
        if k not in self._horizon_fns:
            def fn(params, pools, tokens, lengths, tables, seeds, temps,
                   top_ks, top_ps, live, budgets, eos_ids, *lora_args):
                def step(carry, _):
                    pools, tok, lens, live, budg = carry
                    eff_tables = jnp.where(live[:, None], tables,
                                           TRASH_PAGE)
                    attend = self.make_attend(eff_tables, lens)
                    logits, cache = self.mod.paged_decode_step(
                        self.config, params, tok[:, None], lens, pools,
                        attend,
                        **({"lora": self._lora_ctx(lora_args)}
                           if lora_args else {}))
                    nxt = _sample_tokens(logits.astype(jnp.float32),
                                         seeds, lens + 1, temps, top_ks,
                                         top_ps)
                    nxt = jnp.where(live, nxt, 0)
                    new_budg = jnp.where(live, budg - 1, budg)
                    hit_eos = jnp.where(eos_ids >= 0, nxt == eos_ids,
                                        False)
                    new_live = live & ~hit_eos & (new_budg > 0)
                    new_lens = jnp.where(live, lens + 1, lens)
                    cache.pop("routing", None)   # the horizon keeps no count
                    return (cache, nxt, new_lens, new_live, new_budg), nxt

                (pools, tok, lens, live, budg), toks = jax.lax.scan(
                    step, (pools, tokens, lengths, live, budgets),
                    None, length=k)
                return toks.T, tok, lens, live, budg, pools

            kv_out = ((self._repl,) * 5 + (self._pool_shardings(),)
                      if self.shard_kv else None)
            self._horizon_fns[k] = jax.jit(
                named(fn, f"serve_horizon_k{k}"), donate_argnums=(1,),
                **({"out_shardings": kv_out} if kv_out else {}))
        return self._horizon_fns[k]

    def chunk_for(self, t: int):
        """The ONE chunk-prefill program: [1, t] tokens run the paged
        decode path — the engine's ``attend_impl`` resolves the
        multi-token attend exactly like the decode step's (the block_q=T
        kernel on TPU under "auto"/"flash": one O(context) read per
        chunk instead of the ~3x gather round-trip) — writing their k/v
        into the slot's pages at positions start..start+t-1 while
        attending over the committed history. ``n_valid`` routes a final
        chunk's pad tail to the trash page; ``last_index`` picks the
        real last token's logits."""
        if t not in self._chunk_fns:
            def fn(params, pools, ids, start, table, last_index, n_valid,
                   *lora_args):
                attend = self.make_attend(table, start, n_valid=n_valid)
                logits, cache = self.mod.paged_decode_step(
                    self.config, params, ids, start, pools,
                    attend, last_index=last_index,
                    **({"lora": self._lora_ctx(lora_args)}
                       if lora_args else {}))
                cache.pop("routing", None)
                return logits[0], cache

            kv_out = ((self._repl, self._pool_shardings())
                      if self.shard_kv else None)
            self._chunk_fns[t] = jax.jit(
                named(fn, f"serve_chunk_t{t}"), donate_argnums=(1,),
                **({"out_shardings": kv_out} if kv_out else {}))
        return self._chunk_fns[t]

    def verify_for(self, t: int, greedy: bool = False):
        """The speculative-verification program: ``[S, t]`` tokens per
        slot (index 0 = the slot's newest sampled token, 1.. = the
        drafter's candidates, zero-padded; ``n_valid`` [S] routes each
        pad tail's scatter to the trash page), ONE forward through the
        multi-token paged path — the same ``[S, T]`` form chunked
        prefill runs, sharded attend included — with ALL-position logits
        and the position-keyed target sampler at every row.

        Returns (targets [S, t], n_acc [S], new_lengths [S], k_pages,
        v_pages): ``targets[s, j]`` is the token the spec-off engine
        would sample at absolute position ``lengths[s] + 1 + j``
        (fold_in(seed, that position) — the deterministic stream), and
        ``n_acc[s]`` counts the leading drafts that EQUAL their target
        draw. Acceptance is therefore exact by construction: the engine
        emits ``targets[s, :n_acc+1]`` — always the target sampler's own
        tokens — and the drafts only decide how many land per weight
        pass (serve/spec.py has the full argument). ``new_lengths`` is
        the post-acceptance rollback (``lengths + n_acc + 1`` per active
        slot — everything past it is dead k/v the next scatter
        overwrites), computed in-program so a steady spec iteration
        keeps lengths ON DEVICE: the host uploads only the candidate ids
        and reads back only (targets, n_acc).

        ``greedy=True`` is a STATIC specialization the engine selects
        when every active slot decodes at temperature 0 (a host-known
        predicate): the per-position draw is
        then exactly ``argmax`` — same output, none of the sampler's
        sorted-space top-k/top-p machinery, which is t full-vocab sorts
        per iteration and dominates the verify cost on CPU. Mixed
        batches (any stochastic slot) take the full sampler program."""
        key = (t, bool(greedy))
        if key not in self._verify_fns:
            def fn(params, pools, ids, lengths, tables, seeds, temps,
                   top_ks, top_ps, actives, n_valid, *lora_args):
                attend = self.make_attend(tables, lengths, n_valid=n_valid)
                logits, cache = self.mod.paged_decode_step(
                    self.config, params, ids, lengths, pools,
                    attend, all_logits=True,
                    **({"lora": self._lora_ctx(lora_args)}
                       if lora_args else {}))
                if greedy:
                    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    pos = lengths[:, None] + 1 + jnp.arange(t)[None, :]
                    targets = jax.vmap(
                        _sample_tokens,
                        in_axes=(1, None, 1, None, None, None),
                        out_axes=1)(logits.astype(jnp.float32), seeds, pos,
                                    temps, top_ks, top_ps)
                targets = jnp.where(actives[:, None], targets, 0)
                matches = ((ids[:, 1:] == targets[:, :-1])
                           & (jnp.arange(t - 1)[None, :]
                              < (n_valid - 1)[:, None]))
                n_acc = jnp.cumprod(matches.astype(jnp.int32),
                                    axis=1).sum(axis=1)
                new_lengths = jnp.where(actives, lengths + n_acc + 1,
                                        lengths)
                cache.pop("routing", None)
                return targets, n_acc, new_lengths, cache

            kv_out = ((self._repl, self._repl, self._repl,
                       self._pool_shardings())
                      if self.shard_kv else None)
            self._verify_fns[key] = jax.jit(
                named(fn, f"serve_verify_t{t}" + ("_greedy" if greedy else "")),
                donate_argnums=(1,),
                **({"out_shardings": kv_out} if kv_out else {}))
        return self._verify_fns[key]

    def launch_sample(self, logit, request: Request, position: int):
        """Batch-1 sample off prefill logits (the request's first token),
        enqueued behind the prefill that makes the logits and LEFT ON THE
        DEVICE: a scalar the host has not read."""
        return self._sample_one(
            logit.astype(jnp.float32),
            jnp.asarray(request.seed, jnp.int32),
            jnp.asarray(position, jnp.int32),
            jnp.asarray(request.temperature, jnp.float32),
            jnp.asarray(request.top_k, jnp.int32),
            jnp.asarray(request.top_p, jnp.float32))

    def sample_one(self, logit, request: Request, position: int) -> int:
        """:meth:`launch_sample` read back to the host at once: the span
        holds the wait for the prefill that made the logits."""
        with span("serve.sample", request_id=request.request_id):
            return int(self.launch_sample(logit, request, position))

    def check_prompt(self, request: Request) -> None:
        """Range-check prompt ids (the scheduler is model-agnostic): under
        jit the embedding gather CLAMPS out-of-range ids, so an unchecked
        prompt would return garbage generations with a 200 instead of
        being refused."""
        v = self.config.vocab_size
        bad = [t for t in request.prompt_ids if not 0 <= int(t) < v]
        if bad:
            raise ValueError(
                f"prompt ids {bad[:5]} out of range for vocab_size {v}")


class ServeEngine(DecodeArrays):
    """Multi-request generation over a model family's KV-cache decode.

    Drive it either through ``serve/api.py`` (``generate_many`` /
    ``serve_http``) or directly: ``submit(Request(...))`` then ``step()``
    in a loop — each ``step`` is one scheduler iteration (deadline expiry
    + grow/preempt + admit + prefill work + one batched decode) and
    returns whatever finished.

    A family whose window layers keep pages of their own
    (``kv_pages.window_layout``) gets its SECOND page class sized by the
    engine: what the slots and one prefill chunk can hold at once
    (``kv_pages.window_pages_bound``), so no reservation can fail.
    ``prefix_cache`` then defaults to off (the family refuses it by name,
    like a decode horizon). A family with a STATE CLASS
    (``kv_pages.sequence_state_layout``: a recurrent state addressed by
    sequence) gets ``n_slots + 1`` blocks of it, one a slot and the trash
    block, so admission never waits on the class.

    ``prefix_cache`` (default on): committed prompt pages register in a
    content-keyed cache so identical prefixes share physical pages across
    requests (refcounted, copy-on-write; a match may end mid-page).
    ``prefill_chunk=N`` streams prompts through the paged path N tokens
    per iteration (a long prompt does not stall resident decodes for its
    full length); None is the engine's own size
    (:func:`resolve_prefill_chunk`). ``attend_impl`` picks the paged
    attend FAMILY for every forward (decode, spec verify, prefill
    chunk): "auto" (flash kernel on TPU, gather elsewhere), "flash",
    "xla" — one family per engine, so identity guarantees never
    straddle kernels. ``max_queue`` bounds the admission queue —
    submits past it refuse with a 429-class RefusalError (backpressure
    the HTTP layer forwards verbatim). ``speculate`` turns on
    speculative decoding
    ("ngram" for the built-in prompt-lookup drafter at depth ``spec_k``,
    or any ``serve/spec.py`` Drafter instance): drafts verify through
    ONE multi-token forward per iteration with exact acceptance —
    spec-on output is token-identical to spec-off at every temperature
    (see serve/spec.py), and acceptance/amortization counters land in
    ``stats()``.

    Under a multi-device ``plan=``, params shard as in training while the
    page pool stays replicated; ``shard_kv=True`` additionally splits the
    pool on the kv-head axis and runs the attend (flash kernel included)
    shard_map'd with per-chip pool slices — the distributed-pool mode
    (tp-only meshes; see serve/sharding.py).

    ``kv_dtype`` ("fp32" | "bf16" | "int8"; default: the model dtype)
    picks the pool's STORAGE: "int8" stores block-wise absmax-quantized
    payloads with per-(position, kv-head) fp32 scales (serve/kv_pages.py)
    — ~0.31x the fp32 pool bytes at head_dim 16 (0.27x at 64), so ~3x
    more pages per pool byte and proportionally less HBM read on the
    bandwidth-bound decode. Every write site quantizes, every read site
    dequantizes (in-kernel on the flash path), and all scheduling
    invariants — bitwise replay, CoW, handoff, spec-on == spec-off —
    carry over because quantization is pure per token. Quality is a
    measurable trade: tests/test_kv_quant.py pins the attend error bound
    and the spec-acceptance delta vs an fp32-KV control.
    """

    def __init__(self, bundle: ModelBundle, params, *, n_slots: int = 8,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 max_len: Optional[int] = None, plan=None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 attend_impl: str = "auto",
                 shard_kv: bool = False, max_queue: Optional[int] = None,
                 programs: Optional[ModelPrograms] = None,
                 speculate=None, spec_k: int = 4, kv_dtype=None,
                 weight_dtype=None, max_adapters: Optional[int] = None,
                 adapter_rank: int = 8, adapter_alpha: float = 16.0,
                 adapter_targets=DEFAULT_TARGETS,
                 host_tier_bytes: Optional[int] = None,
                 decode_horizon: int = 1):
        if decode_horizon < 1:
            raise ValueError(f"decode_horizon must be >= 1, got "
                             f"{decode_horizon}")
        if decode_horizon > 1 and speculate is not None:
            raise ValueError(
                f"speculate={speculate!r} with decode_horizon="
                f"{decode_horizon}: speculative decoding requires K=1 "
                f"this release — the verify program is already "
                f"multi-token, and fusing it under a horizon is named "
                f"follow-on work. Drop one of the two knobs.")
        self.decode_horizon = decode_horizon
        mod = programs.mod if programs is not None else family_module(
            bundle.family)
        refuse_for_family(mod, bundle.family, {
            "speculate": speculate is not None,
            "host_tier_bytes": host_tier_bytes is not None,
            "prefix_cache": bool(prefix_cache),
            "decode_horizon": decode_horizon > 1})
        if prefix_cache is None:    # on, unless the family cannot serve it
            prefix_cache = "prefix_cache" not in getattr(
                mod, "SERVE_REFUSES", {})
        self.drafter = resolve_drafter(speculate, spec_k=spec_k,
                                       n_slots=n_slots)
        self.spec = new_spec_counters()
        # spec-on == spec-off identity needs ONE program family for every
        # emitted token — and since the block_q=T kernel, "auto" IS one
        # family: the Mosaic gate is T-independent, so decode, verify,
        # and replay all resolve to flash (TPU, eligible shapes) or all
        # to gather. The construction-time downgrade to "xla" that used
        # to live here is gone — flash-everywhere is the default forward.
        self.programs = programs if programs is not None else ModelPrograms(
            bundle, params, plan=plan, shard_kv=shard_kv,
            attend_impl=attend_impl, kv_dtype=kv_dtype,
            weight_dtype=weight_dtype, max_adapters=max_adapters,
            adapter_rank=adapter_rank, adapter_alpha=adapter_alpha,
            adapter_targets=adapter_targets)
        self.bundle = self.programs.bundle
        self.kv_dtype = self.programs.kv_dtype
        # like kv_dtype: when a pre-built ``programs`` is shared in, the
        # storage dtypes are ITS dtypes — the kwarg only shapes a fresh
        # ModelPrograms (spawned replicas inherit the fleet's precision)
        self.weight_dtype = self.programs.weight_dtype
        # shared-programs inheritance, like the dtypes: a spawned replica
        # or a disagg pair serves the FLEET's pool, never a private one
        self.adapter_pool = self.programs.adapter_pool
        self.config = self.programs.config
        self.mod = self.programs.mod
        self.plan = self.programs.plan
        self.attend_impl = self.programs.attend_impl
        max_len, self.max_model_len, self.max_pages = \
            resolve_context_bounds(self.config, max_len, page_size)
        self.prefill_chunk = resolve_prefill_chunk(
            prefill_chunk, max_pages=self.max_pages, page_size=page_size)
        # a forced 'flash' the compiled kernel cannot take fails here, at
        # construction, not inside the first forward of a live request
        resolve_attend_for(self.config, self.attend_impl, page_size)
        self.page_size = page_size
        self.n_slots = n_slots
        if n_pages is None:
            # default: full residency + the trash page — backpressure /
            # preemption only engage when the caller sizes the pool below
            n_pages = 1 + n_slots * self.max_pages
        # a family with a second page class (kv_pages.window_layout): its
        # window layers' pages, held only while a query can see them. The
        # class is sized so that no reservation can fail, and no larger: a
        # bounded few pages a slot and one chunk's worth
        second = window_layout(self.config)
        n_window_pages = None if second is None else window_pages_bound(
            second["window"], page_size, n_slots, self.prefill_chunk)
        # a family with a state class (kv_pages.sequence_state_layout): a
        # block a slot, and block 0 for the slots that hold no sequence
        n_state_blocks = (None if sequence_state_layout(self.config) is None
                          else n_slots + 1)
        pool = PagePool(n_pages, page_size, n_window_pages, n_state_blocks)
        self.scheduler = Scheduler(
            n_slots=n_slots, pool=pool, max_len=self.max_model_len,
            max_pages_per_slot=self.max_pages, prefix_cache=prefix_cache,
            max_queue=max_queue,
            # admission headroom scales to the k in-flight speculated
            # tokens a verify step can scatter per running decode
            spec_lookahead=self.drafter.k if self.drafter else 0,
            adapter_pool=self.adapter_pool,
            decode_horizon=decode_horizon,
            # a page's recurrent-state row is the state at its last token
            partial_page_hits=state_layout(self.config) is None,
            window=None if second is None else second["window"])

        self.pages = self.programs.init_device_pages(
            n_pages, page_size, n_window_pages, n_state_blocks)

        # host-RAM KV tier (serve/tiering.py): spilled prefix pages and
        # preempted sequences park here instead of being recomputed.
        # Spilled pages FREE their HBM slots, so the base pool identity
        # (free + held + cached == capacity) is unchanged — the tier
        # audits its own byte ledger separately.
        self.host_tier: Optional[HostTier] = None
        if host_tier_bytes is not None:
            self.host_tier = HostTier(host_tier_bytes)
            gather = make_gather(self)
            self.scheduler.attach_tier(self.host_tier, gather)
            if self.scheduler.cache is not None:
                self.scheduler.cache.attach_tier(self.host_tier, gather)
            self.programs.attach_host_tier(self.host_tier)

        # chunked-prefill state per slot + the device-resident steady
        # decode arrays (no_dev = the next decode uploads them all from the
        # scheduler; DecodeArrays has the states)
        self._pending: dict[int, Admission] = {}
        self._dev = no_dev("first")
        # this step's completed prefills: how many, and the (admission,
        # token) of those whose first token is sampled and still on the
        # device (_on_prefill_complete); both empty between steps
        self._step_prefills = 0
        self._first: list[tuple] = []
        # steps in which a prefill completed, and those of them whose decode
        # was dispatched before the first token was read (stats())
        self.chunk_steps = 0
        self.chunk_steps_overlapped = 0
        install_gc_span()
        # the decode program that is enqueued and not yet booked, plain
        # or horizon (dispatch_decode / dispatch_horizon's record): the
        # double buffer's ONE slot — the device runs it while the host books
        # the one before, returns to its caller and is called again
        self._inflight: Optional[dict] = None
        # what `settle` finished outside a step: the next step returns it
        self._settled: list[RequestResult] = []
        self.draining = False
        # decode throughput + latency counters (api.py metrics; all
        # host-side — see stats())
        self.decode_steps = 0
        self.decode_tokens = 0
        # every step under the order it took, and the step's first failing
        # quiet test under its cause (step(): what the spans' `order` and
        # `held_by` say, for the operator who has no profiler session)
        self.steps_by_order = dict.fromkeys(STEP_ORDERS, 0)
        self.not_quiet = dict.fromkeys(NOT_QUIET, 0)
        self.host_dispatches = 0
        self.horizon_ksum = 0
        self._lat = LatencyMeter()
        # monotone per-ITERATION sequence number surfaced in stats(): a
        # poller seeing the same value twice knows the snapshot is stale
        # (the engine has not iterated between reads), which is how the
        # control plane distinguishes "idle but alive" from "wedged"
        # without trusting the snapshot's own timestamps
        self.stats_seq = 0
        # set_speculation(False) parks the drafter here so a later
        # set_speculation(True) restores the SAME drafter (spec-on ==
        # spec-off identity is what makes the mid-stream toggle legal)
        self._parked_drafter = None

    # ---- delegation (kept public: tests and benchmarks lower these) --------
    @property
    def params(self):
        return self.programs.params

    @property
    def _decode_fn(self):
        return self.programs._decode_fn

    # ---- serving loop ------------------------------------------------------
    def submit(self, request: Request) -> int:
        if self.draining:
            self.scheduler.refuse(
                "draining",
                "engine is draining: finishing in-flight work, not "
                "accepting new requests", http_status=503,
                retry_after_s=self.scheduler.retry_after_hint())
        try:
            self.programs.check_prompt(request)
        except ValueError as exc:
            self.scheduler.refuse("bad_prompt", str(exc))
        return self.scheduler.submit(request)

    def resubmit(self, request: Request, generated=(), *,
                 first_token_at: float = 0.0,
                 submitted_at: Optional[float] = None) -> int:
        """Router fence recovery: re-admit a request that already ran on
        a dead/wedged replica. The prompt re-prefills and the recorded
        ``generated`` tokens REPLAY through the decode program — the
        replicas share params, so position-keyed sampling makes the
        continuation token-identical to the uninterrupted run (the same
        bitwise-recompute rule preemption already owns).

        ``submitted_at`` is the FIRST client submit time: without it the
        scheduler restamps its own clock at requeue, and every TTFT or
        deadline measured afterwards silently forgets the time the
        request already spent queued, running, and bouncing between
        replicas — a resubmitted request would get a fresh deadline per
        hop."""
        if self.draining:
            self.scheduler.refuse(
                "draining", "engine is draining: not accepting resubmits",
                http_status=503)
        return self.scheduler.requeue(request, generated,
                                      first_token_at=first_token_at,
                                      submitted_at=submitted_at)

    def drain(self) -> None:
        """Stop admitting; in-flight work runs to completion through
        step() as usual — the graceful half of SIGTERM/stop. The router
        reads ``draining`` from stats() and marks this replica
        unroutable; the HTTP worker keeps stepping until pending futures
        empty (api.py ``_EngineWorker.stop(drain=True)``). A flag and
        nothing else, as that caller is another thread than the one that
        steps: a decode program in flight is booked by the steps that
        follow, and ``has_work`` holds until it is."""
        self.draining = True

    def settle(self) -> None:
        """Book the decode program in flight NOW, outside a step, on the
        thread that steps: the scheduler then holds every token the device
        has made, and the next step runs the synchronous order on it. For
        what reads or rewrites the host's state WHOLE between two steps: an
        engine swap's export (``serve/elastic.py``) and a forced publish.
        Everything else that changes what a step may do is seen by the next
        step's quiet test, which drains (a drafter or a horizon switched
        on, a queued request, a deadline), or is ordered behind the program
        on the device and needs nothing of the host (``gather_pages`` /
        ``scatter_pages``: the pool's arrays are that program's outputs).
        Requests that the booking finished (an eos) are returned by the
        next ``step``, or taken by ``take_settled``; ``has_work`` holds
        until then."""
        if self._inflight is not None:
            fin = self._book_inflight()
            self._lat.note(fin)
            self._settled.extend(fin)

    def take_settled(self) -> list[RequestResult]:
        """The finished requests ``settle`` holds, handed over once."""
        settled, self._settled = self._settled, []
        return settled

    def set_speculation(self, on: bool) -> bool:
        """Turn speculative decoding on/off at an iteration boundary —
        the controller's load actuation. Drafting spends extra compute
        per iteration to shorten per-request latency; under a saturated
        batch that compute is better spent on the batch itself, so the
        control plane parks the drafter at high load and restores it
        when traffic thins. Legal mid-stream BECAUSE spec-on == spec-off
        is a token-identity invariant (the verifier only ever accepts
        what the plain path would have sampled); in-flight sequences
        continue bitwise across the toggle. No-op (returns False) when
        the engine was built without a drafter. The admission margin
        (``spec_lookahead``) stays at the drafter's k even while parked
        — conservative, and it means re-enabling never over-admits.
        Returns whether speculation is on after the call."""
        if on and self.decode_horizon > 1:
            raise ValueError(
                f"set_speculation(True) with decode_horizon="
                f"{self.decode_horizon}: speculative decoding requires "
                f"K=1 this release — set_decode_horizon(1) first")
        if on and self.drafter is None and self._parked_drafter is not None:
            self.drafter = self._parked_drafter
            self._parked_drafter = None
            self.drop_dev("speculation")
        elif not on and self.drafter is not None:
            self._parked_drafter = self.drafter
            self.drafter = None
            self.drop_dev("speculation")
        return self.drafter is not None

    def set_decode_horizon(self, k: int) -> int:
        """Set the fused-decode horizon at an iteration boundary — the
        controller's dispatch-amortization actuation (K grows under
        batch/throughput pressure, shrinks to 1 under streaming/deadline
        pressure: a K-horizon emits tokens in K-bursts, so per-token p99
        ITL rises toward K·step even while throughput improves). Legal
        mid-stream BECAUSE the horizon is observation granularity, not
        semantics: position-keyed sampling makes the K-step stream
        token-identical to K single steps, so in-flight sequences
        continue bitwise across the change. Any in-flight block (or plain
        program) finishes booking under its own dispatched K, in the next
        step, which drains; admission margins follow the new K
        immediately. Returns the horizon now in force."""
        if k < 1:
            raise ValueError(f"decode_horizon must be >= 1, got {k}")
        if k > 1 and (self.drafter is not None
                      or self._parked_drafter is not None):
            raise ValueError(
                f"set_decode_horizon({k}) on an engine built with a "
                f"drafter: speculative decoding requires K=1 this "
                f"release — the verify program is already multi-token, "
                f"and fusing it under a horizon is named follow-on work")
        self.decode_horizon = k
        self.scheduler.decode_horizon = k
        return self.decode_horizon

    def publish_params(self, new_params, *, force: bool = False) -> int:
        """Publish refreshed weights into the shared program cache
        (``ModelPrograms.publish_params`` — layout-validated, retrace-free
        buffer swap). The post-training loop's policy-update seam.

        Refused while the engine holds IN-FLIGHT work unless ``force``:
        every identity guarantee in this package (preemption replay,
        spec-on == spec-off, resubmission recovery) assumes one set of
        weights per token stream, and a mid-stream publish would make a
        later bitwise REPLAY of already-emitted tokens diverge from the
        recording. The on-policy loop publishes between rollout batches,
        when the engine is drained — exactly the safe window. ``force``
        is for callers that accept mid-stream policy changes and forgo
        replay identity for the sequences in flight."""
        if not force and self.has_work:
            raise RuntimeError(
                f"publish_params with "
                f"{len(self.scheduler.queue)} queued + "
                f"{len(self.scheduler.active_indices()) + len(self.scheduler.prefilling_indices())} "
                f"resident sequences in flight: a mid-stream weight swap "
                f"breaks bitwise replay for them (preemption/resubmit "
                f"would rewrite history under new weights) — finish or "
                f"drain first, or pass force=True to accept that")
        self.settle()   # forced mid-stream: what is in flight ran the old
        return self.programs.publish_params(new_params)

    def publish_adapter(self, adapter_params, *,
                        name: Optional[str] = None,
                        slot: Optional[int] = None,
                        force: bool = False) -> int:
        """Publish ONE tenant adapter into the shared pool
        (``ModelPrograms.publish_adapter`` — validated, retrace-free).
        Returns the slot id requests carry as ``adapter_id``.

        Refused while the engine holds in-flight work unless ``force``,
        mirroring ``publish_params``: a republish into a live slot would
        rewrite a mid-stream tenant's weights (breaking bitwise replay
        for its sequences), and even a fresh insert can LRU-recycle a
        slot id an about-to-replay sequence still names. The post loop
        publishes between rollout batches — the drained window. The
        recycled slot's prefix-cache namespace is dropped here: cached
        k/v computed under the old tenant must never serve the new one."""
        if not force and self.has_work:
            raise RuntimeError(
                f"publish_adapter with "
                f"{len(self.scheduler.queue)} queued + "
                f"{len(self.scheduler.active_indices()) + len(self.scheduler.prefilling_indices())} "
                f"resident sequences in flight — finish or drain first, "
                f"or pass force=True to accept mid-stream adapter churn")
        self.settle()
        slot_id = self.programs.publish_adapter(adapter_params, name=name,
                                                slot=slot)
        if self.scheduler.cache is not None:
            self.scheduler.cache.drop_namespace(slot_id)
        return slot_id

    def evict_adapter(self, slot: int) -> None:
        """Retire a tenant adapter (refuses while its requests are in
        flight — AdapterPool.evict) and drop its prefix-cache namespace:
        the slot id is about to be recycled, and a stale cached page
        under it would silently corrupt the next tenant's prompts."""
        if self.adapter_pool is None:
            raise ValueError("this engine serves no adapter pool (built "
                             "with max_adapters=None)")
        self.adapter_pool.evict(slot)
        if self.scheduler.cache is not None:
            self.scheduler.cache.drop_namespace(slot)

    @property
    def decode_steps_pipelined(self) -> int:
        """Steps that enqueued their decode program BEFORE they read the one
        in flight: a view of ``steps_by_order``."""
        return self.steps_by_order["pipelined"]

    @property
    def has_work(self) -> bool:
        """Queued or resident sequences, a program in flight (its lanes'
        requests may all have ended by an eos the host has yet to read), or
        a finished request ``settle`` holds for the next step."""
        return (self.scheduler.has_work or self._inflight is not None
                or bool(self._settled))

    def kv_cache_bytes(self) -> int:
        """Resident KV bytes — scales with the page pool, NOT with
        n_slots x max_len (the memory pin in tests/test_serve.py). Summed
        over the pool's LEAVES (``kv_pages.pool_nbytes``), so a quantized
        pool's fp32 scales are counted, not hidden. Global bytes: under
        shard_kv each chip holds 1/tp of this."""
        return pool_nbytes(self.pages)

    def _on_prefill_complete(self, adm: Admission,
                             logit) -> Optional[RequestResult]:
        """The slot's pages are fully committed: it joins the decode
        batch (device arrays rebuild) with its first token sampled —
        unless it is a resumed sequence, whose tokens already exist (the
        host has them: nothing to sample, nothing to read).

        On the plain path the first token is sampled and LEFT on the
        device: the step goes on to build and dispatch the decode while the
        chunk program runs, and reads the token after that
        (``run_decode_iteration``'s ``first``). The older order, the token
        read here, before anything else of the step, is kept where the
        step cannot know that the plain single-token program comes next, or
        gains nothing by it:

        - a drafter is configured: whether the verify program runs is
          decided by what the drafter proposes, from the host's tokens;
        - ``decode_horizon > 1``: the horizon's lanes (``budgets``, the
          live mask) are built from the host's record of each slot;
        - ``max_new_tokens == 1``: the first token ends the request, known
          beforehand, and the slot never decodes.

        And a token left here is read before growth after all where the
        step's growth will preempt (``_iterate``): a victim goes back to the
        queue, or to the host tier, with every token it has."""
        self.drop_dev("prefilled")
        self._step_prefills += 1
        if adm.resumed:
            return None
        req = adm.request
        if (self.drafter is not None or self.decode_horizon > 1
                or req.max_new_tokens <= 1):
            t0 = self.programs.sample_one(logit, req, len(adm.tokens))
            return self.scheduler.record_token(adm.slot_idx, t0,
                                               from_decode=False)
        with span("serve.sample", request_id=req.request_id):
            self._first.append((adm, self.programs.launch_sample(
                logit, req, len(adm.tokens))))
        return None

    def _horizon_ready(self) -> bool:
        """Whether the active batch may run a fused K-step horizon: the
        knob is up, no drafter (spec stays K=1 this release), and no
        slot is mid-replay (a post-preemption replay must rewrite k/v
        through the SAME single-token program that wrote it)."""
        sched = self.scheduler
        return (self.decode_horizon > 1 and self.drafter is None
                and not any(sched.slots[i].replaying
                            for i in sched.active_indices()))

    def _pipeline_steady(self) -> str:
        """What keeps the NEXT decode program, plain or horizon, from being
        enqueued before the pending one is booked, the FIRST of the checks
        that fails (one of ``utils/trace.py``'s ``NOT_QUIET``), or ``""``:
        no scheduler event can need the host state the pending tokens
        carry. Slots are decoding (else ``inactive``), no drafter (what it
        proposes comes from the host's tokens), nothing ``queued`` that
        might get in (a head whose last refusal still stands,
        ``Scheduler.head_refusal_stands``, would be refused again: nothing
        a drain could learn; what ends the refusal, a reply's end, is the
        ``budget`` check's to see a step ahead), no ``prefill`` pending or
        running, no slot ``replaying`` (it consumes recorded tokens, from
        the host) and no ``deadline`` due, a queued request's too (expiry
        stays a boundary event)."""
        sched = self.scheduler
        active = sched.active_indices()
        if not active:
            return "inactive"
        if self.drafter is not None:
            return "drafter"
        if sched.queue and not sched.head_refusal_stands():
            return "queued"
        if self._pending or sched.prefilling_indices():
            return "prefill"
        if any(sched.slots[i].replaying for i in active):
            return "replaying"
        if sched.deadline_due():
            return "deadline"
        return ""

    def _ahead(self, pending_k: int, first: list = (),
               resident: Optional[str] = None) -> tuple[Optional[int], str]:
        """The QUIET test, one for both decode programs, as a span where it
        runs (``serve.quiet``: ``held_by``, the first check that failed or
        ``""``; ``_quiet`` has the checks). Returns ``(k, "")``: how many
        steps the program AFTER the pending ones may run, enqueued before
        they are read; or ``(None, held_by)``: the pending tokens are then
        read first, and the boundary runs on authoritative host state."""
        with span("serve.quiet") as sp:
            k, held_by = self._quiet(pending_k, first, resident)
            sp.set_metadata(held_by=held_by)
        return k, held_by

    def _quiet(self, pending_k: int, first: list,
               resident: Optional[str]) -> tuple[Optional[int], str]:
        """``(k, "")`` or ``(None, cause)``. ``pending_k``
        device steps are not booked yet: a program in flight, whose
        ``resident`` arrays the one after it needs as they stand on the
        device, tables apart (the host's tokens and lengths are ``pending_k``
        behind: a whole set cannot go up from them); or the one this step is
        about to enqueue (``first``: its slots whose first token is still on
        the device).

        The next program may go up where the one in flight is of the
        ``kind`` the engine would enqueue now, the pipeline is steady
        (``_pipeline_steady``) and the
        pages of its writes fit without preempting: they are reserved here,
        ``pending_k`` writes past the host's lengths, which lag the
        device's by as much (``reserve_ahead``). A horizon masks a lane that
        ends inside the pending block in-device (finishes hiding there are
        fine: booking them after the dispatch frees their pages for the
        NEXT boundary) and is only clamped to the largest budget left; the
        plain program masks nothing, so a budget that ends with a pending
        token is a boundary (an eos cannot be known: ``book_inflight`` has
        the rule for that one lane)."""
        sched = self.scheduler
        if resident is not None and resident != (
                "plain" if self.decode_horizon == 1 else "horizon"):
            return None, "kind"
        held_by = self._pipeline_steady()
        if held_by:
            return None, held_by
        if (self.decode_horizon == 1 or sched.queue) \
                and sched.min_remaining_budget(
                    {adm.slot_idx for adm, _ in first}) <= pending_k:
            # a horizon too where a refused head waits for what the ending
            # reply returns: it is admitted in the step that books the end
            return None, "budget"
        covered = self.reserve_ahead(sched, pending_k + self.decode_horizon)
        if resident is not None and self._dev["kind"] != resident:
            # the arrays went: a lane left and they name it still (or the
            # reservation's growth dropped them, which stale_tables never
            # does); the pages just taken arrive early
            return None, "arrays"
        if covered - pending_k < 1:
            return None, "pages"
        # clamp by the largest remaining budget MINUS the steps already
        # pending: when they provably finish every slot, k drops below 1
        # and the step drains instead of burning an all-dead trailing
        # horizon
        k = min(covered - pending_k, self.decode_horizon,
                sched.max_remaining_budget() - pending_k)
        return (k, "") if k >= 1 else (None, "budget")

    def _book_inflight(self, behind: Optional[dict] = None) -> list:
        """Read and book the program in flight; ``behind``, the one this
        step enqueued after it (or None), takes its place. The arrays on
        the device go where they name a lane that is gone: a plain program
        whose booking finished a request (the one enqueued behind it ran
        that lane once more, ``book_inflight``), a horizon that drains."""
        pending, self._inflight = self._inflight, behind
        fin, emitted = book_inflight(self.programs, self.scheduler, pending)
        self.decode_tokens += emitted
        if pending["kind"] == "plain":
            if fin:
                self.drop_dev("left")
        elif behind is None:
            self.drop_dev("drained")
        return fin

    def _note_dispatch(self, k: int) -> None:
        self.host_dispatches += 1
        self.horizon_ksum += k
        self.decode_steps += k

    def step(self) -> list[RequestResult]:
        """One scheduler iteration, which books ONE decode token for every
        decoding slot (a horizon: one block) and returns what finished. It
        takes one of two orders, by what it can observe and no knob.

        SYNCHRONOUS, with nothing in flight at its start: expire deadlines
        (clean eviction at the boundary), admit whatever now fits (sharing
        cached prefixes), advance prefill work (one chunk-budget's worth),
        grow the decoding slots (preempting the cheapest on true
        exhaustion), then ONE batched decode over the decoding slots,
        enqueued and read in this step (a fused K-step horizon at
        ``decode_horizon > 1`` is enqueued and left in flight).

        A synchronous step that COMPLETES a prefill on the plain path
        dispatches its two programs back to back and reads the host once:
        the chunk program is enqueued; while it runs the first token is
        sampled and seated on the device, the slots grow, the decode
        arrays are built and go up and the decode program is enqueued
        behind the chunk; only then does the host read, the first token and
        then the decode's tokens (``_on_prefill_complete`` has the paths
        that keep the older order, the first token read before anything
        else; ``stats()`` counts both kinds, ``chunk_steps`` /
        ``chunk_steps_overlapped``).

        PIPELINED, with a decode program D(n) in flight at its start and
        the step QUIET (``_ahead``: slots decoding, nothing queued that
        might get in, no prefill pending, no deadline due, no drafter, no
        replaying slot, no budget that ends with token n, and the pages of
        write n+1 fit without preempting): the pages of write n+1 are
        reserved, the block tables alone go up if they grew, D(n+1) is
        enqueued on the tokens and lengths D(n) leaves on the device, and
        THEN the host waits on D(n) and books it. D(n+1) runs while the host books, returns to its
        caller and is called again: the round trip, the booking and the
        caller's own bookkeeping cost the device nothing
        (``serve.step``'s ``order`` names the order each step took, one of
        ``utils/trace.py``'s ``STEP_ORDERS``, and ``stats()["steps_by_order"]``
        counts them; ``serve.wait``'s ``waits_for`` names the step that
        enqueued what was read). A synchronous plain step that
        ends quiet ENTERS the pipeline: it enqueues D(n) and D(n+1) under
        its one ``serve.dispatch`` and reads D(n) alone.

        With a program in flight and the step NOT quiet, the plain pipeline
        DRAINS: D(n) is waited on and booked, and the step returns; the
        boundary (expiry, admission, a chunk, preempting growth, a
        budget's end) runs in the next step, synchronous, on authoritative
        host state. A horizon's drain books its block and goes on into the
        boundary in the same step, as it has no token of this step's to
        book twice. Finished results therefore surface at most one step
        after their tokens were computed.

        A queue head whose last refusal STILL STANDS does not make a step
        unquiet (``Scheduler.head_refusal_stands``: the same head, nothing
        come back to the pool or the slots since, the headroom not lower, no
        host tier): a real attempt would be refused again, so the step goes
        ahead past it, makes none, and says that the head waited there too
        (``Scheduler.hold_head``: a ``serve.admit`` span with ``held`` 1;
        ``stats()["admission_held"]``). What ends the refusal is a reply's
        end, which the ``budget`` check sees a step ahead (for a horizon
        too while a head is held): that step drains, its booking returns
        the slot and the pages, and the next step, synchronous, admits: the
        step the parent's order admitted in. An end by EOS, read behind an
        enqueued program, costs the head one step, as it costs an empty
        queue's refill.

        One lane too many: a token n that ends its request by EOS means
        D(n+1) ran that lane once more. Its token is not booked (lanes are
        matched by request id), and its write went to a page and a state
        block that the slot owned and has freed, which no later program
        reads before it writes them (whatever the pool re-issues is written
        by a program enqueued after D(n+1); a state block's next owner
        starts from zeros). The arrays on the device are dropped, so the
        next step drains. What booking n RELEASES is what D(n+1) does not
        read: a window layer's page is released by the host's length, which
        is D(n+1)'s own (one behind the device's once D(n+1) has run: late,
        never early).

        What needs the host's state whole: a drafter or a horizon switched
        on, a request that arrived (or a head whose refusal ended), a
        deadline and a lane that left are seen by the next step's quiet
        test, which drains; an engine swap and a forced publish book what
        is in flight at once, outside a step
        (``settle``); ``drain`` stays a flag (another thread may call it)
        and ``gather_pages`` / ``scatter_pages`` are ordered behind the
        program on the device. ``partial_tokens`` is what has been BOOKED,
        and ``has_work`` holds while a program is in flight."""
        if getattr(self, "_publish_pending_swap", False):
            raise RuntimeError(
                "new_generation(params=...) already published the next "
                "policy into this engine's shared programs — stepping it "
                "before swap_generation would decode old-policy k/v "
                "under the new weights and the replay would preserve the "
                "mixed-policy tokens; run the swap (or build the new "
                "generation without params=)")
        self.stats_seq += 1
        settled = self.take_settled()
        # the whole iteration as one host span; its children (expire,
        # restore, admit, fork, prefill, sample, reserve, build, dispatch,
        # wait, book) are emitted where that work happens
        with span("serve.step", seq=self.stats_seq) as sp:
            cpu0 = time.thread_time()
            overlapped = self.chunk_steps_overlapped
            finished, order, held_by = self._iterate()
            self.steps_by_order[order] += 1
            if held_by:
                self.not_quiet[held_by] += 1
            sp.set_metadata(
                cpu_ms=1e3 * (time.thread_time() - cpu0),
                overlapped=self.chunk_steps_overlapped - overlapped,
                order=order)
            return settled + finished

    def _iterate(self) -> tuple[list[RequestResult], str, str]:
        """The iteration, the order it took (``STEP_ORDERS``) and what its
        FIRST failing quiet test named (``NOT_QUIET``; a horizon's drain may
        run the plain program, and its test, in the same step), ``""``
        where none ran or none failed."""
        finished = []
        sched = self.scheduler
        order, held_by = "idle", ""
        if self._inflight is not None:
            kind = self._inflight["kind"]
            k, held_by = self._ahead(self._inflight["k"], resident=kind)
            order = "drain" if k is None else "pipelined"
            behind = None
            if k is not None:
                if sched.queue:
                    # quiet with a request queued: its refusal stands, and
                    # the step says that the head waited here too
                    sched.hold_head()
                if kind == "plain":
                    (behind,), self._dev = dispatch_decode(
                        self.programs, self.pages, sched, self._dev,
                        seq=self.stats_seq)
                else:
                    behind, self._dev = dispatch_horizon(
                        self.programs, self.pages, sched, self._dev, k,
                        seq=self.stats_seq)
                self._note_dispatch(k)
            fin = self._book_inflight(behind)
            if behind is not None or kind == "plain":
                # pipelined, or a plain drain: this step's token is booked,
                # and the boundary is the next step's
                self._lat.note(fin)
                return fin, order, held_by
            # a horizon's drain: a boundary event needs host state the
            # pending block still held; the device arrays are rebuilt after
            # the boundary runs
            finished.extend(fin)
        expired = sched.expire_deadlines()
        if expired:
            self.drop_dev("expired")
            drop_stale_pending(sched, self._pending)
            finished.extend(expired)
        if self.host_tier is not None:
            # restore AHEAD of admission: a queued request whose pages
            # sit in the host tier seats by scatter (bitwise, replay_pos
            # intact) instead of re-prefilling, and a queue head whose
            # prefix chain was spilled gets its pages re-seated in the
            # cache so the ordinary shared-prefix admission path finds
            # them. Both paths allocate from the SAME free list admission
            # uses, so the audit identity is untouched.
            with span("serve.restore"):
                if restore_queued(sched, self.host_tier, self.scatter_pages,
                                  self._tier_alloc):
                    self.drop_dev("restored")
                if sched.queue and sched.cache is not None:
                    head = sched.queue[0].request
                    restore_prefixes(
                        sched.cache, self.host_tier, list(head.prompt_ids),
                        ns=int(getattr(head, "adapter_id", 0) or 0),
                        alloc=self._tier_alloc, scatter=self.scatter_pages,
                        free=sched.pool.free)
        admissions = sched.try_admit()
        for adm in admissions:
            self.drop_dev("admitted")
            if adm.fork is not None:
                run_fork(self.programs, self.pages, adm)
            self._pending[adm.slot_idx] = adm
        self._step_prefills = 0
        if self._pending:
            finished.extend(advance_prefill_chunks(
                self.programs, self.pages, sched, self._pending,
                self.prefill_chunk, self._on_prefill_complete))
        self.chunk_steps += bool(self._step_prefills)

        # growth runs LAST before the decode so every slot in the batch —
        # including one admitted or chunk-completed this very iteration
        # whose prefill ended exactly on a page boundary — owns the page
        # its next write lands in. That holds in a chunk step's order too:
        # the chunk program may still be running, but what comes after
        # growth is the build of the decode arrays and the decode's
        # dispatch, and nothing of the scheduler's in between
        if self._first and not sched.growth_fits():
            # growth will preempt, and a victim takes with it what the host
            # has of it (its tokens to the queue, its pages to a host tier):
            # the first tokens are read now, the older order
            finished.extend(book_first_tokens(sched, self._first)[0])
            self._first = []
        grown, preempted = sched.grow_for_decode()
        if preempted:           # a slot left the batch
            self.drop_dev("preempted")
            drop_stale_pending(sched, self._pending)
        elif grown:             # the same slots, longer block tables
            self.stale_tables("grown")
        first, self._first = self._first, []
        self.chunk_steps_overlapped += bool(first)

        if sched.active_indices():
            if self._horizon_ready():
                # grow_for_decode already guaranteed every slot's next
                # write (preempt discipline), so coverage is >= 1; the
                # reservation only decides how much of K the pool grants,
                # and the budget clamp keeps the final horizon of a
                # batch from running steps past every slot's max_new
                k0 = max(1, min(
                    self.reserve_ahead(sched, self.decode_horizon),
                    self.decode_horizon, sched.max_remaining_budget()))
                self._inflight, self._dev = dispatch_horizon(
                    self.programs, self.pages, sched, self._dev, k0,
                    seq=self.stats_seq)
                self._note_dispatch(k0)
                # no blocking read here: the block books next step (or at
                # the next drain) — the first half of the double buffer
                if order != "drain":
                    order = "enter"
            else:
                # the plain program enters the pipeline where the step ends
                # quiet: the one after it goes up behind it, unread
                k, cause = self._ahead(1, first)
                ahead, held_by = k is not None, held_by or cause
                fin, emitted, self._dev, self._inflight = \
                    run_decode_iteration(
                        self.programs, self.pages, sched, self.drafter,
                        self.spec, self._dev, first, seq=self.stats_seq,
                        ahead=ahead)
                for _ in range(1 + ahead):
                    self._note_dispatch(1)
                self.decode_tokens += emitted
                finished.extend(fin)
                if fin:
                    self.drop_dev("left")
                if order != "drain":
                    order = "enter" if ahead else "sync"
        self._lat.note(finished)
        return finished, order, held_by

    # ---- host tier plumbing ------------------------------------------------
    def gather_pages(self, page_ids) -> dict:
        """Bitwise host copy of the given pages, every pool leaf (int8
        payload AND scale rows) — the tier's and the wire's unit. A read
        of the pool alone, ordered on the device behind a decode program
        in flight (the arrays are its outputs); the scheduler is not
        touched."""
        return gather_payload(self.pages, list(page_ids))

    def scatter_pages(self, page_ids, payload) -> None:
        """Seat a gathered payload back into this engine's pool at the
        given (freshly allocated) page ids. Functional pool update, so
        the device decode arrays must rebuild (with a program in flight
        the scatter is ordered behind it, and the next step drains)."""
        out = scatter_payload(self.pages, list(page_ids), payload)
        for name in out:
            self.pages[name] = out[name]
        self.drop_dev("restored")

    def _tier_alloc(self, n: int):
        """Allocate ``n`` pages for a restore, refusing unless the free
        list keeps one page of growth headroom per active decode slot —
        a restore must never force-preempt the running batch it is
        trying to hide under."""
        sched = self.scheduler
        headroom = len(sched.active_indices())
        if sched.pool.n_free < n + headroom:
            return None
        return sched.pool.alloc(n)

    def restore_adapter(self, name: str):
        """Re-seat a host-spilled adapter's A/B rows into the device
        stacks (satellite: spill past max_adapters without a fleet
        republish). Legal while serving: AdapterPool.alloc only recycles
        refcount-0 slots, and the recycled slot's prefix namespace is
        dropped exactly as publish_adapter would."""
        slot_id = self.programs.restore_adapter(name)
        if slot_id is not None and self.scheduler.cache is not None:
            self.scheduler.cache.drop_namespace(slot_id)
        return slot_id

    # ---- metrics (host-side only — safe from any thread) -------------------
    def partial_tokens(self) -> dict:
        """request_id -> tokens generated so far, for every LIVE sequence
        — the streaming layer's tap. Pure host bookkeeping (the tokens
        were already read back for EOS checks), so the HTTP worker can
        push per-token deltas without extra device traffic. The consumer
        contract (dedup-by-count; a speculative iteration's accepted run
        flushes at once) is documented on ``collect_partial_tokens``."""
        return collect_partial_tokens([self.scheduler])

    def stats(self) -> dict:
        """Metrics snapshot WITHOUT acquiring the device or any lock:
        every value is host-side Python the scheduler/engine already
        maintains, so ``/healthz`` answers mid-decode-iteration (reads
        are individually atomic under the GIL; the snapshot is
        best-effort consistent, which is what a health probe wants).

        Two of its keys say what a profiler session would, without one.
        ``steps_by_order``: every step since the engine was built under the
        order it took (``utils/trace.py``'s ``STEP_ORDERS``);
        ``decode_steps_pipelined`` is its ``pipelined``. ``not_quiet``: each
        ``sync`` and ``drain`` step under the first thing that kept it from
        pipelining (``NOT_QUIET``). To read them: ``queued`` counts the
        drains for a request that MIGHT get in, an arrival under a program
        in flight or a head whose refusal ended: it rises with the arrival
        rate, not with the queue's depth. A head the pool goes on refusing
        costs no drain: ``admission_held`` counts the steps that went ahead
        past it (``admission_blocked`` the real attempts the pool refused),
        so ``admission_held`` high beside ``budget`` in step with
        ``finished`` is a pool that admits only as replies end, with the
        pipeline flowing in between; ``budget`` and ``arrays`` in step with
        ``finished``: replies ending, the price of a closed loop;
        ``prefill``: prompts longer than a chunk."""
        sched = self.scheduler
        s = {k: (dict(v) if isinstance(v, dict) else v)
             for k, v in sched.stats.items()}
        return {
            **s,
            "stats_seq": self.stats_seq,
            "preemptions": s.get("preempted", 0),
            "decode_horizon": self.decode_horizon,
            "decode_steps_pipelined": self.decode_steps_pipelined,
            "steps_by_order": dict(self.steps_by_order),
            "not_quiet": dict(self.not_quiet),
            "draining": self.draining,
            "max_queue": sched.max_queue,
            "queued": len(sched.queue),
            "queue_depth_by_priority": sched.queue_depth_by_priority(),
            "active_slots": len(sched.active_indices()),
            "prefilling_slots": len(sched.prefilling_indices()),
            "prefill_calls": self.programs.prefill_calls,
            "chunk_steps": self.chunk_steps,
            "chunk_steps_overlapped": self.chunk_steps_overlapped,
            "live_pages_by_class": sched.live_pages_by_class(),
            "state_blocks_live": sched.live_state_blocks(),
            **({"routing": dict(self.programs.routing)}
               if self.programs.routing["steps"] else {}),
            # committed prefix keys for the router's fleet directory —
            # read lock-free from the same snapshot, fenced by stats_seq
            "prefix_keys": (cache_prefix_keys(sched.cache)
                            if sched.cache is not None else []),
            **derived_pool_metrics(
                tier=self.host_tier,
                pool=sched.pool, cached_pages=sched.cache_pages_held(),
                n_slots=self.n_slots, decode_steps=self.decode_steps,
                decode_tokens=self.decode_tokens,
                host_dispatches=self.host_dispatches,
                horizon_ksum=self.horizon_ksum,
                admitted=s.get("admitted", 0),
                prefix_hits=s.get("prefix_hits", 0), lat=self._lat,
                bytes_per_page=kv_page_bytes(self.config,
                                             page_size=self.page_size,
                                             kv_dtype=self.kv_dtype),
                pool_dtype=self.kv_dtype),
            **spec_metrics(self.spec, decode_steps=self.decode_steps,
                           decode_tokens=self.decode_tokens,
                           drafter=self.drafter),
            **adapter_metrics(
                self.adapter_pool,
                publishes=self.programs.adapter_publish_count),
        }

    def kv_report(self) -> dict:
        """The preflight-style byte table for this engine's pool."""
        return build_kv_report(
            self.programs, page_size=self.page_size,
            pool=self.scheduler.pool,
            cached_pages=self.scheduler.cache_pages_held(),
            n_slots=self.n_slots, max_pages=self.max_pages,
            pool_bytes=self.kv_cache_bytes(), tier=self.host_tier,
            decode_horizon=self.decode_horizon)

    def weight_report(self) -> dict:
        """The preflight-style byte table for this engine's weights."""
        return build_weight_report(self.programs)

    def adapter_report(self) -> dict:
        """The preflight-style byte table for this engine's adapter pool
        (empty without one)."""
        return build_adapter_report(self.programs)

    def weight_bytes(self) -> int:
        """Actual param storage bytes (int8 payload + scales under
        weight_dtype='int8') — the weights twin of kv_cache_bytes."""
        return params_nbytes(self.programs.params)
