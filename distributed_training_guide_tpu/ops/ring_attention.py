"""Zigzag ring attention: context parallelism over the ``cp`` mesh axis.

The reference name-checks context parallelism ("For long context lengths",
``06-tensor-parallel/README.md:7``) but never implements it — its long-context
story is flash-attn + activation checkpointing + a seq-length flag. For the
TPU build CP is first-class: the sequence dim of the *batch and activations*
is sharded over ``cp`` contiguously (plain GSPMD sharding — data pipeline,
RoPE and loss never see anything unusual), and attention — the only op that
crosses sequence shards — runs inside a shard_map where only ``cp`` is
manual:

- **zigzag load balance**: under causal masking, contiguous shards give rank
  cp-1 ~cp x the work of rank 0 (it attends to every earlier shard). Here the
  sequence is viewed as 2*cp chunks and two static ppermutes re-layout each
  rank's (q, k, v) to the zigzag pair (chunk r, chunk 2cp-1-r) before the
  ring, so every rank owns one early and one late chunk — per-rank live
  chunk-pairs are (r+1) + (2cp-r) = 2cp+1, identical for all ranks. Outputs
  are re-layouted back, so the wrapper is layout-transparent.
- **ring**: K/V zigzag blocks rotate via ``jax.lax.ppermute`` (neighbor ICI
  hops), overlapping transfer with compute; per-pair partial results merge
  with the standard (o, lse) online-softmax combine in fp32.
- **flash kernel per chunk pair**: each live (q-chunk, kv-chunk) pair runs
  the Pallas flash kernel (``flash_attention._flash_fwd``) on the
  ``[B, S_c, H, D]`` chunks as the ring holds them (no relayout of its own)
  — scores never materialize outside VMEM tiles, and GQA is kernel-native
  (no K/V expansion). Future pairs are *skipped* by ``lax.cond`` (no
  FLOPs issued);
  diagonal pairs use the kernel's causal mode.
- **hand-written ring backward** (``jax.custom_vjp``): the backward re-runs
  the ring with the *global* logsumexp and ``delta = rowsum(do*o)`` feeding
  ``flash_bwd_with_stats`` per pair — the flash-attention identity that
  makes per-chunk gradient contributions exact without any full attention
  matrix. dk/dv accumulators travel the ring *with* their K/V blocks and
  arrive home after a full cycle.

tp composes: heads (tp) and batch (dp/fsdp/ep) are *manual* axes of the
same shard_map — the Pallas calls inside the ring are Mosaic custom calls
the SPMD partitioner cannot shard, so leaving them auto would gather and
replicate every hop's chunks across dp/tp on a real pod. The body needs no
collectives over those axes (attention is independent per batch and head),
so only cp carries ppermutes. Round 1's partitioner CHECK came from
auto-tp *weights* inside a manual region; q/k/v here are already-projected
activations, which shard cleanly.

On non-TPU backends the same kernels run under ``interpret=True`` — the
test-suite goldens (forward and gradients vs the dense XLA reference) cover
exactly this code path.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from .dispatch import note_attention, resolve_interpret
from .flash_attention import (_flash_fwd, _pack_band, check_static_window,
                              flash_bwd_with_stats, row_dots)

NEG_INF = -1e30


def _zigzag_perms(cp: int):
    """Static ppermute lists for contiguous->zigzag relayout.

    Contiguous rank r holds chunks (2r, 2r+1); zigzag rank r holds chunks
    (r, 2cp-1-r). Chunk c's zigzag owner is c if c < cp else 2cp-1-c. Each
    rank's half-h block (chunk 2r+h) has one destination -> one static perm
    per half.
    """
    def owner(c):
        return c if c < cp else 2 * cp - 1 - c

    perm0 = [(r, owner(2 * r)) for r in range(cp)]
    perm1 = [(r, owner(2 * r + 1)) for r in range(cp)]
    inv0 = [(d, s) for (s, d) in perm0]
    inv1 = [(d, s) for (s, d) in perm1]
    return perm0, perm1, inv0, inv1


def _to_zigzag(x, idx, axis_name, cp):
    """[B, S_loc, ...] contiguous shard -> [B, 2, S_c, ...] zigzag chunks."""
    b, s_loc = x.shape[:2]
    s_c = s_loc // 2
    halves = x.reshape(b, 2, s_c, *x.shape[2:])
    perm0, perm1, _, _ = _zigzag_perms(cp)
    recv_a = jax.lax.ppermute(halves[:, 0], axis_name, perm0)
    recv_b = jax.lax.ppermute(halves[:, 1], axis_name, perm1)
    # chunk r has parity r%2 -> arrives via that perm; chunk 2cp-1-r has the
    # opposite parity (2cp-1-r == 1-r mod 2), so there is never a collision
    even = (idx % 2) == 0
    slot0 = jnp.where(even, recv_a, recv_b)
    slot1 = jnp.where(even, recv_b, recv_a)
    return jnp.stack([slot0, slot1], axis=1)


def _from_zigzag(x, idx, axis_name, cp):
    """Inverse of ``_to_zigzag``: [B, 2, S_c, ...] -> [B, S_loc, ...]."""
    _, _, inv0, inv1 = _zigzag_perms(cp)
    even = (idx % 2) == 0
    # undo the slot selection, then the permutes
    recv_a = jnp.where(even, x[:, 0], x[:, 1])
    recv_b = jnp.where(even, x[:, 1], x[:, 0])
    half0 = jax.lax.ppermute(recv_a, axis_name, inv0)
    half1 = jax.lax.ppermute(recv_b, axis_name, inv1)
    stacked = jnp.stack([half0, half1], axis=1)
    b = x.shape[0]
    return stacked.reshape(b, -1, *x.shape[3:])


def _merge(o, lse, o_i, lse_i):
    """Combine two normalized flash partials (o ``[B,S,H,D]`` fp32 as the
    kernels take and return it, lse ``[B,H,S]`` fp32 as they keep it: the
    weights, one a row, cross to o's layout)."""
    mx = jnp.maximum(lse, lse_i)
    mx_safe = jnp.where(mx < NEG_INF / 2, 0.0, mx)  # both-empty rows
    w0 = jnp.exp(lse - mx_safe)
    w1 = jnp.exp(lse_i - mx_safe)
    tot = w0 + w1
    safe_tot = jnp.where(tot == 0.0, 1.0, tot)
    w0_o, w1_o, tot_o = (w.swapaxes(1, 2)[..., None]
                         for w in (w0, w1, safe_tot))
    o_new = (o * w0_o + o_i * w1_o) / tot_o
    lse_new = jnp.where(tot == 0.0, NEG_INF, mx_safe + jnp.log(safe_tot))
    return o_new, lse_new


def _relation(kv_chunk, q_chunk, causal):
    """0 past (full attention) / 1 diagonal (causal) / 2 future (skip)."""
    if not causal:
        return jnp.int32(0)
    return jnp.where(kv_chunk == q_chunk, 1,
                     jnp.where(kv_chunk < q_chunk, 0, 2))


def _pair_live(kv_chunk, q_chunk, s_c, window):
    """Banded-mode chunk-pair skip predicate: a (q-chunk, kv-chunk) pair is
    dead when it is FUTURE (kv newer than q) or when every key in the kv
    chunk falls below the sliding-window band of every query in the q chunk
    — the zigzag analogue of the kernel's ``_band_live`` tile skip, at
    chunk granularity. ``window`` is a traced scalar (per-layer schedules);
    2**30 encodes "full attention this layer" and keeps every past pair
    live."""
    live = kv_chunk <= q_chunk
    # newest key in the kv chunk still inside the OLDEST query's window
    live &= (kv_chunk + 1) * s_c - 1 >= q_chunk * s_c - (window - 1)
    return live


def _build_ring(axis_name: str, cp: int, causal: bool, interpret: bool,
                use_scan: bool, scale=None, softcap=None):
    """Per-shard fwd/bwd ring bodies (flash kernel per chunk pair). The
    custom_vjp pairing them lives OUTSIDE the shard_map (make_ring_attention)
    so shard_map's own transpose machinery is never engaged.

    ``member`` (size-1 int32, the cp-sharded iota) carries this member's
    ring position instead of ``jax.lax.axis_index``: when the ring nests
    inside the pipeline's pp-manual region, Shardy lowers axis_index of an
    auto-queried axis as a manual computation over the *complement* axes —
    which re-binds pp and is rejected ("already bound by a parent"). A
    sharded iota argument carries the same value with no such lowering.

    ``use_scan``: roll the cp hops into one ``lax.scan`` iteration instead
    of Python-unrolling them. The per-pair relation codes are traced values
    either way (they derive from the member index), so the two forms are
    op-for-op identical per hop — the scan form just makes program size and
    trace/compile time O(1) in cp instead of O(cp), at the cost of one
    extra (unused) kv rotation on the final hop. ``make_ring_attention``
    picks scan automatically at large cp."""
    ring = [(i, (i + 1) % cp) for i in range(cp)]

    def _fwd_pairs(qz, k_blk, v_blk, o, lse, my_chunks, kv_chunks,
                   window=None, s_c=None):
        """The 4 (q-chunk, kv-chunk) flash calls of one hop, merged into
        the running (o, lse). Future pairs — and, in banded mode, pairs
        fully below the sliding-window band — skip inside the cond, merge
        included, so they issue no work. In banded mode (``window`` a
        traced scalar) every live pair runs the kernel causal with its
        GLOBAL chunk offsets riding the dynamic band operand: diagonal and
        past pairs share one program, and the in-kernel band mask is exact
        across chunk boundaries."""
        for a in range(2):
            for c in range(2):
                qa, kc, vc = qz[:, a], k_blk[:, c], v_blk[:, c]
                o_a, lse_a = o[:, a], lse[:, a]

                if window is not None:
                    band = _pack_band(window, my_chunks[a] * s_c,
                                      kv_chunks[c] * s_c)

                    def live_banded(qa=qa, kc=kc, vc=vc, o_a=o_a,
                                    lse_a=lse_a, band=band):
                        o_i, lse_i = _flash_fwd(
                            qa, kc, vc, True, None, 512, 512, interpret,
                            scale=scale, softcap=softcap, band=band)
                        return _merge(o_a, lse_a, o_i.astype(jnp.float32),
                                      lse_i)

                    o_a, lse_a = jax.lax.cond(
                        _pair_live(kv_chunks[c], my_chunks[a], s_c, window),
                        live_banded, lambda: (o_a, lse_a))
                else:
                    rel = _relation(kv_chunks[c], my_chunks[a], causal)

                    def live(masked, qa=qa, kc=kc, vc=vc, o_a=o_a,
                             lse_a=lse_a):
                        o_i, lse_i = _flash_fwd(qa, kc, vc, masked, None,
                                                512, 512, interpret,
                                                scale=scale, softcap=softcap)
                        return _merge(o_a, lse_a, o_i.astype(jnp.float32),
                                      lse_i)

                    o_a, lse_a = jax.lax.cond(
                        rel >= 2, lambda: (o_a, lse_a),
                        lambda: jax.lax.cond(rel == 1,
                                             functools.partial(live, True),
                                             functools.partial(live, False)))
                o = o.at[:, a].set(o_a)
                lse = lse.at[:, a].set(lse_a)
        return o, lse

    def ring_fwd_body(member, q, k, v, window=None):
        idx = member[0]
        b, s_loc, hq, d = q.shape
        hkv = k.shape[2]
        if s_loc % 2:
            raise ValueError(f"local sequence {s_loc} must be even (2*cp "
                             f"chunks); pad seq to a multiple of {2 * cp}")
        s_c = s_loc // 2

        # zigzag chunks [B, 2, S_c, H, D]: a chunk goes to the kernels as
        # it lies; lse stays as they keep it, [B, 2, H, S_c]
        qz = _to_zigzag(q, idx, axis_name, cp)
        kz = _to_zigzag(k, idx, axis_name, cp)
        vz = _to_zigzag(v, idx, axis_name, cp)

        my_chunks = (idx, 2 * cp - 1 - idx)
        w = None if window is None else window[0]

        o = jnp.zeros((b, 2, s_c, hq, d), jnp.float32)
        lse = jnp.full((b, 2, hq, s_c), NEG_INF, jnp.float32)

        if use_scan:
            def hop(carry, i):
                k_blk, v_blk, o, lse = carry
                src = (idx - i) % cp
                o, lse = _fwd_pairs(qz, k_blk, v_blk, o, lse, my_chunks,
                                    (src, 2 * cp - 1 - src), w, s_c)
                k_blk = jax.lax.ppermute(k_blk, axis_name, ring)
                v_blk = jax.lax.ppermute(v_blk, axis_name, ring)
                return (k_blk, v_blk, o, lse), None

            (_, _, o, lse), _ = jax.lax.scan(hop, (kz, vz, o, lse),
                                             jnp.arange(cp))
        else:
            k_blk, v_blk = kz, vz
            for i in range(cp):
                src = (idx - i) % cp
                if i < cp - 1:
                    k_nxt = jax.lax.ppermute(k_blk, axis_name, ring)
                    v_nxt = jax.lax.ppermute(v_blk, axis_name, ring)
                o, lse = _fwd_pairs(qz, k_blk, v_blk, o, lse, my_chunks,
                                    (src, 2 * cp - 1 - src), w, s_c)
                if i < cp - 1:
                    k_blk, v_blk = k_nxt, v_nxt

        out = _from_zigzag(o.astype(q.dtype), idx, axis_name, cp)
        # ONLY the primal output + seq-layout lse leave the map (cf. the
        # sharded-flash wrapper): a shard_map eqn is atomic under
        # jax.checkpoint's partial-eval, so zigzag-layout residual outputs
        # would force the whole fwd ring — cp-1 kv rotations and every
        # flash kernel — to re-run in backward just to rebuild relayouts.
        # The bwd body re-zigzags from the raw inputs + saved outputs
        # instead (a few ppermutes), which is what lets the
        # REMAT_POLICIES["attn"] tags actually skip the fwd ring.
        lse_seq = _from_zigzag(lse.swapaxes(2, 3), idx, axis_name, cp)
        return out, lse_seq

    def ring_bwd_body(member, q, k, v, out, lse_seq, do, window=None):
        in_dtype = q.dtype
        idx = member[0]
        my_chunks = (idx, 2 * cp - 1 - idx)
        w = None if window is None else window[0]

        # rebuild the zigzag chunks the fwd used (cheap ppermutes; see the
        # fwd-body note on why these are not residuals)
        qz = _to_zigzag(q, idx, axis_name, cp)
        kz = _to_zigzag(k, idx, axis_name, cp)
        vz = _to_zigzag(v, idx, axis_name, cp)
        o = _to_zigzag(out, idx, axis_name, cp).astype(jnp.float32)
        lse = _to_zigzag(lse_seq, idx, axis_name, cp).swapaxes(2, 3)

        doz = _to_zigzag(do, idx, axis_name, cp).astype(jnp.float32)
        # global softmax stats: the flash-bwd identity needs the FINAL lse and
        # delta = rowsum(do * o_final) — per-pair contributions then sum to
        # the exact gradient
        delta = jnp.stack([row_dots(doz[:, a], o[:, a]) for a in range(2)],
                          axis=1)                              # [B,2,H,S_c]

        dq = jnp.zeros(qz.shape, jnp.float32)
        dk = jnp.zeros(kz.shape, jnp.float32)
        dv = jnp.zeros(vz.shape, jnp.float32)

        s_c = qz.shape[2]

        def _bwd_pairs(k_blk, v_blk, dq, dk, dv, kv_chunks):
            """One hop's 4 flash-bwd calls; accumulation runs INSIDE the
            cond so skipped pairs cost nothing in the backward either.
            Banded mode mirrors the forward exactly: the same global-offset
            band rides the bwd kernels (the score recompute must reproduce
            the fwd mask for the flash-bwd identity to hold), and the same
            chunk-pair skip predicate keeps dead pairs free."""
            for a in range(2):
                for c in range(2):
                    qa, kc, vc = qz[:, a], k_blk[:, c], v_blk[:, c]
                    doa, lsea, dta = doz[:, a], lse[:, a], delta[:, a]
                    dq_a, dk_c, dv_c = dq[:, a], dk[:, c], dv[:, c]

                    if w is not None:
                        band = _pack_band(w, my_chunks[a] * s_c,
                                          kv_chunks[c] * s_c)

                        def live_banded(qa=qa, kc=kc, vc=vc, doa=doa,
                                        lsea=lsea, dta=dta, dq_a=dq_a,
                                        dk_c=dk_c, dv_c=dv_c, band=band):
                            dq_i, dk_i, dv_i = flash_bwd_with_stats(
                                qa, kc, vc, doa.astype(qa.dtype), lsea, dta,
                                causal=True, interpret=interpret,
                                scale=scale, softcap=softcap, band=band)
                            return (dq_a + dq_i.astype(jnp.float32),
                                    dk_c + dk_i.astype(jnp.float32),
                                    dv_c + dv_i.astype(jnp.float32))

                        dq_a, dk_c, dv_c = jax.lax.cond(
                            _pair_live(kv_chunks[c], my_chunks[a], s_c, w),
                            live_banded, lambda: (dq_a, dk_c, dv_c))
                    else:
                        rel = _relation(kv_chunks[c], my_chunks[a], causal)

                        def live(masked, qa=qa, kc=kc, vc=vc, doa=doa,
                                 lsea=lsea, dta=dta, dq_a=dq_a, dk_c=dk_c,
                                 dv_c=dv_c):
                            dq_i, dk_i, dv_i = flash_bwd_with_stats(
                                qa, kc, vc, doa.astype(qa.dtype), lsea, dta,
                                causal=masked, interpret=interpret,
                                scale=scale, softcap=softcap)
                            return (dq_a + dq_i.astype(jnp.float32),
                                    dk_c + dk_i.astype(jnp.float32),
                                    dv_c + dv_i.astype(jnp.float32))

                        dq_a, dk_c, dv_c = jax.lax.cond(
                            rel >= 2, lambda: (dq_a, dk_c, dv_c),
                            lambda: jax.lax.cond(
                                rel == 1, functools.partial(live, True),
                                functools.partial(live, False)))
                    dq = dq.at[:, a].set(dq_a)
                    dk = dk.at[:, c].set(dk_c)
                    dv = dv.at[:, c].set(dv_c)
            return dq, dk, dv

        if use_scan:
            def hop(carry, i):
                k_blk, v_blk, dq, dk, dv = carry
                src = (idx - i) % cp
                dq, dk, dv = _bwd_pairs(k_blk, v_blk, dq, dk, dv,
                                        (src, 2 * cp - 1 - src))
                # dk/dv travel with their K/V blocks: after the final
                # compute one more hop completes the cycle and delivers
                # them to their owners
                dk = jax.lax.ppermute(dk, axis_name, ring)
                dv = jax.lax.ppermute(dv, axis_name, ring)
                k_blk = jax.lax.ppermute(k_blk, axis_name, ring)
                v_blk = jax.lax.ppermute(v_blk, axis_name, ring)
                return (k_blk, v_blk, dq, dk, dv), None

            (_, _, dq, dk, dv), _ = jax.lax.scan(hop, (kz, vz, dq, dk, dv),
                                                 jnp.arange(cp))
        else:
            k_blk, v_blk = kz, vz
            for i in range(cp):
                src = (idx - i) % cp
                if i < cp - 1:
                    k_nxt = jax.lax.ppermute(k_blk, axis_name, ring)
                    v_nxt = jax.lax.ppermute(v_blk, axis_name, ring)
                dq, dk, dv = _bwd_pairs(k_blk, v_blk, dq, dk, dv,
                                        (src, 2 * cp - 1 - src))
                # dk/dv travel with their K/V blocks (see the scan form)
                dk = jax.lax.ppermute(dk, axis_name, ring)
                dv = jax.lax.ppermute(dv, axis_name, ring)
                if i < cp - 1:
                    k_blk, v_blk = k_nxt, v_nxt

        def back(x):
            return _from_zigzag(x.astype(in_dtype), idx, axis_name, cp)

        return back(dq), back(dk), back(dv)

    return ring_fwd_body, ring_bwd_body


def make_ring_attention(mesh: Mesh, *, axis_name: str = "cp",
                        data_axes=("dp", "fsdp", "ep"), head_axis: str = "tp",
                        causal: bool = True,
                        hop_loop: str = "auto",
                        window=None,
                        scale=None,
                        logit_softcap=None) -> Callable:
    """Returns an attention callable with the ``multihead_attention``
    signature, internally a shard_map ring over ``axis_name``.

    Batch and head dims are manual too (over ``data_axes`` / ``head_axis``
    when those mesh axes are >1): the Pallas calls inside the ring are
    Mosaic custom calls, which the SPMD partitioner cannot shard — leaving
    dp/tp auto here would gather-and-replicate q/k/v chunks per hop on a
    real pod (same failure ``make_sharded_flash_attention`` guards on the
    cp=1 path). The body needs no collectives over those axes, so the ring
    logic is unchanged; only cp carries ppermutes. The round-1 partitioner
    CHECK that forced partial-manual was auto-*tp on weights* inside a
    manual region — q/k/v here are activations, already projected.

    ``window``: sliding-window attention (HF semantics) through the zigzag
    ring. Every live (q-chunk, kv-chunk) pair runs the kernel with its
    GLOBAL chunk offsets on the dynamic band operand, so the band mask is
    exact across chunk boundaries, and chunk pairs fully below the band are
    skipped at the hop level (``_pair_live``) on top of the kernel's own
    tile skipping. A per-call ``window`` (traced per-layer schedules,
    Gemma-2) overrides the factory default. ``scale``/``logit_softcap``:
    Gemma-2 score scale / tanh capping, threaded into every per-pair kernel
    call forward and backward (the (o, lse) merge is softcap-agnostic — the
    cap applies per score before each pair's softmax)."""
    from .flash_attention import (_UNSET, _in_manual_context,
                                  attention_divisibility_error,
                                  resolve_attention_manual_axes,
                                  wrapper_shard_map)

    if window is not None and not causal:
        raise ValueError(
            "window (sliding-window attention) requires causal=True")
    check_static_window(window)
    cp = mesh.shape[axis_name]
    batch_axes, head_axis, tp, batch_div, b_spec, _ = \
        resolve_attention_manual_axes(mesh, data_axes, head_axis)
    interpret = resolve_interpret(None)
    spec = P(b_spec, axis_name, head_axis, None)   # [B, S_loc, H, D]
    lse_spec = P(b_spec, axis_name, head_axis)     # [B, S_loc, H]

    if hop_loop not in ("auto", "scan", "unrolled"):
        raise ValueError(f"hop_loop must be 'auto', 'scan', or 'unrolled'; "
                         f"got {hop_loop!r}")
    # program size (and trace/compile time) of the unrolled hops is O(cp) —
    # measured ~2x per cp doubling (08-context-parallel/README.md). The
    # scan form is O(1); per hop the two are op-for-op identical, so at
    # large cp scan is strictly better and 'auto' switches over.
    use_scan = cp >= 8 if hop_loop == "auto" else hop_loop == "scan"
    fwd_body, bwd_body = _build_ring(axis_name, cp, causal, interpret,
                                     use_scan, scale=scale,
                                     softcap=logit_softcap)

    def _maps(banded=False):
        # check_vma=False: pallas interpret mode (the CPU test path) trips
        # the vma checker inside its own lowering ("dynamic_slice requires
        # varying manual axes to match")
        sm = wrapper_shard_map(mesh)
        member = P(axis_name)   # [cp] iota -> each member's ring position
        if banded:
            # the window rides as a replicated [1] int32 operand so traced
            # per-layer schedules (a lax.scan column) reach every member
            wspec = P(None)
            fwd = sm(lambda m, w, q, k, v: fwd_body(m, q, k, v, w),
                     in_specs=(member, wspec, spec, spec, spec),
                     out_specs=(spec, lse_spec))
            bwd = sm(lambda m, w, *a: bwd_body(m, *a, window=w),
                     in_specs=(member, wspec, spec, spec, spec, spec,
                               lse_spec, spec),
                     out_specs=(spec, spec, spec))
        else:
            fwd = sm(fwd_body, in_specs=(member, spec, spec, spec),
                     out_specs=(spec, lse_spec))
            bwd = sm(bwd_body,
                     in_specs=(member, spec, spec, spec, spec, lse_spec,
                               spec),
                     out_specs=(spec, spec, spec))
        return fwd, bwd

    # the custom_vjp sits OUTSIDE the shard_maps: jax.grad never transposes
    # through a partial-manual shard_map (which check_vma=False forbids) —
    # forward and backward are each a plain, non-differentiated shard_map
    @jax.custom_vjp
    def ring(q, k, v):
        members = jnp.arange(cp, dtype=jnp.int32)
        return _maps()[0](members, q, k, v)[0]

    def ring_vjp_fwd(q, k, v):
        members = jnp.arange(cp, dtype=jnp.int32)
        out, lse_seq = _maps()[0](members, q, k, v)
        # the REMAT_POLICIES["attn"] tags, as in the flash wrappers: with
        # these saved, backward runs only the bwd ring — never the fwd one
        out = checkpoint_name(out, "flash_out")
        lse_seq = checkpoint_name(lse_seq, "flash_lse")
        return out, (q, k, v, out, lse_seq)

    def ring_vjp_bwd(res, do):
        members = jnp.arange(cp, dtype=jnp.int32)
        return _maps()[1](members, *res, do)

    ring.defvjp(ring_vjp_fwd, ring_vjp_bwd)

    # banded twin: same rings with the [1] int32 window operand (integer-
    # valued, so its cotangent is float0 like the flash wrapper's band)
    @jax.custom_vjp
    def ring_banded(q, k, v, w):
        members = jnp.arange(cp, dtype=jnp.int32)
        return _maps(banded=True)[0](members, w, q, k, v)[0]

    def ring_banded_vjp_fwd(q, k, v, w):
        members = jnp.arange(cp, dtype=jnp.int32)
        out, lse_seq = _maps(banded=True)[0](members, w, q, k, v)
        out = checkpoint_name(out, "flash_out")
        lse_seq = checkpoint_name(lse_seq, "flash_lse")
        return out, (q, k, v, out, lse_seq, w)

    def ring_banded_vjp_bwd(res, do):
        *res_, w = res
        members = jnp.arange(cp, dtype=jnp.int32)
        grads = _maps(banded=True)[1](members, w, *res_, do)
        return (*grads, np.zeros(w.shape, jax.dtypes.float0))

    ring_banded.defvjp(ring_banded_vjp_fwd, ring_banded_vjp_bwd)
    # partial-manual shard_map only resolves its auto-axes shardings under
    # jit (the eager path rejects the specs), so every top-level call —
    # eager OR traced — goes through this jit. ONLY manual-context callers
    # (the pipeline) bypass it for the raw custom_vjp: this jit's cache must
    # hold concrete-mesh programs exclusively, never a context-mesh trace
    ring_eager = jax.jit(ring)
    ring_banded_eager = jax.jit(ring_banded)

    window_default = window

    def attention(q, k, v, standard_layout: bool = True, window=_UNSET,
                  **kwargs):
        wcall = window_default if window is _UNSET else window
        if not interpret and (q.shape[1] % (16 * cp) or q.shape[-1] % 64):
            # mirror flash_attention's loud guard: per-chunk seq must tile
            # (S/(2cp) % 8) and head_dim must fill MXU lanes, else Mosaic
            # fails opaquely
            raise ValueError(
                f"ring flash attention needs seq divisible by {16 * cp} "
                f"(8-token tiles per zigzag chunk) and head_dim divisible by "
                f"64; got seq={q.shape[1]}, head_dim={q.shape[-1]} — pad the "
                f"sequence or lower cp")
        if not standard_layout:
            raise ValueError(
                "ring attention assumes contiguous positions (rank r owns "
                "[r*S/cp, (r+1)*S/cp)); caller-supplied positions would "
                "desynchronize the causal mask — don't pass explicit "
                "positions under context parallelism")
        if wcall is not None and not causal:
            raise ValueError(
                "window (sliding-window attention) requires causal=True")
        check_static_window(wcall)
        note_attention("ring", f"cp={cp}: context-parallel ring")
        hq, hkv = q.shape[2], k.shape[2]
        if hq % tp or hkv % tp or q.shape[0] % batch_div:
            raise ValueError(attention_divisibility_error(
                batch_axes, head_axis, tp, batch_div, hq, hkv, q.shape[0],
                "ring attention"))
        in_manual = _in_manual_context()
        if wcall is None:
            if in_manual:
                # nested in the pipeline's manual region — by construction
                # under the caller's jit already; the raw custom_vjp builds
                # its maps against the context mesh (the eager jit's cache
                # must never mix top-level and in-pipeline programs)
                return ring(q, k, v)
            return ring_eager(q, k, v)
        warr = jnp.reshape(jnp.asarray(wcall, jnp.int32), (1,))
        if in_manual:
            return ring_banded(q, k, v, warr)
        return ring_banded_eager(q, k, v, warr)

    attention.accepts_window = True
    return attention
