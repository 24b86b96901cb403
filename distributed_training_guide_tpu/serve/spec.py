"""Speculative decoding — the drafting half (Leviathan et al.,
arXiv:2211.17192; Chen et al., arXiv:2302.01318).

Decode is memory-bound: every generated token pays one full pass over
the weights. Speculative decoding amortizes that pass over k candidate
tokens — a DRAFTER proposes k cheap candidates per slot, ONE multi-token
verification forward through the paged KV cache (the chunked-prefill
``[S, T]`` form, ``serve/engine.py`` ``ModelPrograms.verify_for``)
scores all of them, and the accepted prefix lands in one weight read.

Acceptance here is EXACT BY CONSTRUCTION, not probabilistic: the
verification pass samples the TARGET token at every drafted position
with the same ``fold_in(seed, absolute position)`` keys the plain decode
path uses, and a draft is accepted exactly when it equals that sample.
Emitted tokens are therefore always the target sampler's own draws —
greedy spec-on is token-identical to spec-off, and temperature > 0
emits literally the spec-off stream (the strongest form of
distribution-exactness); drafts only decide how many of its tokens land
per weight pass. This is the deterministic-coupling variant of the
rejection-sampling scheme: sharing the acceptance randomness with the
target sampler costs some acceptance rate at temperature > 0
(P[draft == target draw] = sum_x q(x)p(x), vs the coupled scheme's
sum_x min(p(x), q(x))) and buys the property the whole serving stack is
pinned on — a request's tokens are a pure function of (seed, position),
whatever was drafted, accepted, or rejected along the way, so
preemption/replay, admission order, and spec-on/off all agree.

Two drafters behind one interface:

- :class:`NgramDrafter` — prompt-lookup decoding (no extra model): the
  context's longest suffix n-gram is matched against the prompt +
  generated history and the tokens that followed its most recent
  earlier occurrence become the candidates. Free, host-side, and strong
  exactly where speculation pays most: grounded/repetitive continuations
  (summarization, code edits, generation cycles).
- :class:`DraftModelDrafter` — a small draft model co-resident with the
  target, with its OWN full-residency paged pool (drafting must never
  contend with the target's pool) and a batched greedy draft loop over
  the engine's slots. The draft cache is reconciled with the true
  context by SYNC-BY-CONTEXT before every proposal round: roll back to
  the longest common prefix (dead k/v is overwritten in place — the
  same rollback discipline the target pool uses), then catch-up chunks
  for whatever the draft missed. Eviction, preemption, re-seating, and
  rejection on the target side therefore need no callbacks.

Drafting is host-side and per-slot; verification and acceptance live in
``serve/engine.py`` (``run_spec_decode``), shared by the monolithic
engine and the disaggregated decode engine.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.registry import ModelBundle, family_module
from ..utils.trace import named
from .kv_pages import (PagePool, init_pages, make_attend, pages_for_tokens,
                       resolve_attend_impl)


def new_spec_counters() -> dict:
    """The host-side speculation counter bag one engine maintains
    (``spec_metrics`` in engine.py derives the stats()/healthz rows)."""
    return {"spec_steps": 0, "tokens_drafted": 0, "tokens_accepted": 0,
            "tokens_rejected": 0}


class Drafter:
    """Per-slot candidate streams for speculative decoding.

    ``k`` bounds the candidates per proposal; ``propose`` returns up to
    ``budget`` (<= k) candidate token ids for one slot given its full
    context (prompt + tokens generated so far). ``propose_many`` is the
    engine's entry point (one call per iteration, every decoding slot at
    once) — the default loops ``propose``; batched drafters override it.

    Drafters may keep per-slot state but must tolerate a slot being
    re-seated with a DIFFERENT sequence at any iteration boundary:
    eviction, preemption, and deadline expiry are invisible here, so any
    state must reconcile from the context alone (see
    :class:`DraftModelDrafter`'s sync-by-context).
    """

    k: int = 0

    def propose(self, slot_idx: int, context: list, budget: int) -> list:
        raise NotImplementedError

    def propose_many(self, contexts: dict, budgets: dict) -> dict:
        return {i: self.propose(i, contexts[i], budgets[i])
                for i in contexts}

    def stats(self) -> dict:
        """Host-side drafter counters (merged into engine stats())."""
        return {}


class NgramDrafter(Drafter):
    """Prompt-lookup drafting: match the context's suffix n-gram against
    the prompt + generated history, longest n first, and propose the
    tokens that followed its MOST RECENT earlier occurrence (recency
    wins — generation cycles and repeated prompt blocks sit near the
    end). No model, no device work; the scan is bounded to the last
    ``max_lookback`` context tokens so the per-iteration host cost stays
    O(n_gram x lookback) however long the context grows — this runs on
    the decode hot path every iteration, and an unbounded scan would
    re-introduce exactly the per-iteration host wall the device-resident
    decode arrays removed."""

    def __init__(self, k: int = 4, max_n: int = 3, min_n: int = 1,
                 max_lookback: int = 512):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if not 1 <= min_n <= max_n:
            raise ValueError(f"need 1 <= min_n <= max_n, got "
                             f"min_n={min_n}, max_n={max_n}")
        if max_lookback < max_n + 1:
            raise ValueError(f"max_lookback ({max_lookback}) must exceed "
                             f"max_n ({max_n})")
        self.k = k
        self.max_n = max_n
        self.min_n = min_n
        self.max_lookback = max_lookback

    def propose(self, slot_idx: int, context: list, budget: int) -> list:
        budget = min(budget, self.k)
        if budget < 1:
            return []
        context = context[-self.max_lookback:]
        for n in range(self.max_n, self.min_n - 1, -1):
            if len(context) <= n:
                continue
            suffix = context[-n:]
            best: list = []
            for j in range(len(context) - n - 1, -1, -1):
                if context[j:j + n] == suffix:
                    cand = context[j + n:j + n + budget]
                    if len(cand) >= budget:
                        # nearest occurrence with a FULL continuation —
                        # matches adjacent to the context's end (short
                        # generation cycles) truncate their candidates,
                        # so recency alone would cap the draft depth at
                        # the cycle length
                        return [int(x) for x in cand]
                    if len(cand) > len(best):
                        best = cand
            if best:
                return [int(x) for x in best]
        return []


class DraftModelDrafter(Drafter):
    """Draft-model drafting: a small family model (any bundle with the
    ``paged_decode_step`` hook) runs a batched GREEDY draft loop over
    the engine's slots, with its own paged pool sized for full residency
    — the draft cache can never contend with (or corrupt) the target's
    pool, and the whole drafter reuses the serve plane's own paged
    machinery instead of growing a second cache format.

    Greedy drafts are deliberate: candidates are guesses at the target
    sampler's deterministic (seed, position) draw, and the draft model's
    argmax is its best single guess; a sampled draft stream would only
    lower the match rate.
    """

    def __init__(self, bundle: ModelBundle, params, *, n_slots: int,
                 max_len: int, k: int = 4, page_size: int = 16,
                 chunk: int = 16, attend_impl: str = "auto"):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.bundle = bundle
        self.config = bundle.config
        self.mod = family_module(bundle.family)
        if not hasattr(self.mod, "paged_decode_step"):
            raise ValueError(
                f"draft family {bundle.family!r} has no paged decode — "
                f"the drafter needs the paged_decode_step hook")
        self.k = k
        self.n_slots = n_slots
        max_pos = getattr(self.config, "max_position_embeddings", None)
        self.max_len = min(max_len, max_pos) if max_pos else max_len
        self.page_size = page_size
        # the DRAFT model's geometry gates the compiled kernel, not the
        # target's — a forced 'flash' it cannot take raises here instead
        # of inside the first draft forward of a live decode iteration
        resolve_attend_impl(attend_impl, self.config.head_size, page_size)
        self.max_pages = pages_for_tokens(self.max_len, page_size)
        n_pages = 1 + n_slots * self.max_pages
        self.pool = PagePool(n_pages, page_size)
        self.pages = init_pages(self.config, n_pages, page_size)
        self.params = params
        self.chunk = chunk
        # the drafter's own forwards ride the same paged dispatch as the
        # target's (the block_q=T kernel under "auto" on TPU) — drafts
        # are guesses, so this is a quality/throughput knob, not an
        # identity one; match the target engine's family for the best
        # self-draft acceptance
        self.attend_impl = attend_impl
        self._slot_pages: list[list] = [[] for _ in range(n_slots)]
        self._consumed: list[list] = [[] for _ in range(n_slots)]
        self._counters = {"draft_model_steps": 0, "catchup_tokens": 0,
                          "resyncs": 0}
        self._step_fn = jax.jit(named(self._step, "serve_draft_step"),
                                donate_argnums=(1, 2))
        self._chunk_fn = jax.jit(named(self._catchup, "serve_draft_catchup"),
                                 donate_argnums=(1, 2))

    # ---- compiled draft programs (the drafter's own jit cache) -------------
    def _step(self, params, kp, vp, tokens, lengths, tables):
        """One batched greedy draft step over [n_slots] lanes (idle lanes
        carry zero tables and write into the trash page)."""
        attend = make_attend(tables, lengths, impl=self.attend_impl)
        logits, cache = self.mod.paged_decode_step(
            self.config, params, tokens[:, None], lengths,
            {"k": kp, "v": vp}, attend)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                cache["k"], cache["v"])

    def _catchup(self, params, kp, vp, ids, start, table, n_valid):
        """Feed one catch-up chunk of a slot's context into the draft
        cache ([1, chunk] padded; the logits are discarded — the chunk
        exists only to write k/v)."""
        attend = make_attend(table, start, impl=self.attend_impl,
                             n_valid=n_valid)
        _, cache = self.mod.paged_decode_step(
            self.config, params, ids, start, {"k": kp, "v": vp}, attend)
        return cache["k"], cache["v"]

    # ---- per-slot cache bookkeeping ----------------------------------------
    def _ensure_pages(self, slot_idx: int, n_tokens: int) -> None:
        """The slot must own pages covering positions 0..n_tokens-1. The
        pool is sized for full residency, so within the drafter's own
        max_len this cannot fail."""
        need = pages_for_tokens(n_tokens, self.page_size)
        pages = self._slot_pages[slot_idx]
        while len(pages) < need:
            got = self.pool.alloc(1)
            assert got is not None, "full-residency draft pool exhausted"
            pages.extend(got)

    def _table_row(self, slot_idx: int) -> np.ndarray:
        row = np.zeros(self.max_pages, np.int32)
        pages = self._slot_pages[slot_idx]
        row[:len(pages)] = pages
        return row

    def _sync(self, slot_idx: int, target: list) -> None:
        """Reconcile the slot's draft cache with ``target`` (the true
        context minus its newest token): roll back to the longest common
        prefix — dead k/v beyond it is simply overwritten in place, the
        same rollback discipline the target pool uses after a rejection
        — then stream catch-up chunks for the remainder."""
        consumed = self._consumed[slot_idx]
        common = 0
        for a, b in zip(consumed, target):
            if a != b:
                break
            common += 1
        if common < len(consumed):
            del consumed[common:]
            self._counters["resyncs"] += 1
        while len(consumed) < len(target):
            start = len(consumed)
            m = min(self.chunk, len(target) - start)
            self._ensure_pages(slot_idx, start + m)
            ids = np.zeros((1, self.chunk), np.int32)
            ids[0, :m] = target[start:start + m]
            self.pages["k"], self.pages["v"] = self._chunk_fn(
                self.params, self.pages["k"], self.pages["v"],
                jnp.asarray(ids), jnp.asarray([start], jnp.int32),
                jnp.asarray(self._table_row(slot_idx)[None]),
                jnp.asarray([m], jnp.int32))
            consumed.extend(int(x) for x in target[start:start + m])
            self._counters["catchup_tokens"] += m

    # ---- the Drafter surface -----------------------------------------------
    def propose(self, slot_idx: int, context: list, budget: int) -> list:
        out = self.propose_many({slot_idx: context}, {slot_idx: budget})
        return out.get(slot_idx, [])

    def propose_many(self, contexts: dict, budgets: dict) -> dict:
        drafts: dict = {i: [] for i in contexts}
        quota: dict = {}
        for i, ctx in contexts.items():
            # the draft loop consumes positions len(ctx)-1 .. len(ctx)-2+b
            # — clip b so the draft model never runs past ITS position
            # table (which may be smaller than the target's)
            b = min(budgets[i], self.k, self.max_len - len(ctx))
            if b < 1 or not ctx:
                continue
            self._sync(i, list(ctx[:-1]))
            self._ensure_pages(i, len(ctx) + b - 1)
            quota[i] = b
        if not quota:
            return drafts
        s = self.n_slots
        tokens = np.zeros(s, np.int32)
        lengths = np.zeros(s, np.int32)
        tables = np.zeros((s, self.max_pages), np.int32)
        for i in quota:
            tokens[i] = contexts[i][-1]
            lengths[i] = len(contexts[i]) - 1
            tables[i] = self._table_row(i)
        tables_dev = jnp.asarray(tables)
        for _ in range(max(quota.values())):
            nxt, self.pages["k"], self.pages["v"] = self._step_fn(
                self.params, self.pages["k"], self.pages["v"],
                jnp.asarray(tokens), jnp.asarray(lengths), tables_dev)
            self._counters["draft_model_steps"] += 1
            nxt = np.asarray(nxt)
            for i, b in quota.items():
                if len(drafts[i]) >= b:
                    continue        # lane frozen: re-feeds the same token
                                    # into the same position (harmless)
                self._consumed[i].append(int(tokens[i]))
                drafts[i].append(int(nxt[i]))
                tokens[i] = nxt[i]
                lengths[i] += 1
        return drafts

    def stats(self) -> dict:
        return dict(self._counters)
