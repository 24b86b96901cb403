"""Attention dispatch: XLA reference path + Pallas flash path.

The reference gets fused attention from the external ``flash-attn`` CUDA wheel
(``05-training-llama-405b/train_llm.py:93``); the TPU-native equivalent is a
Pallas kernel (``ops/flash_attention.py``). This module is the dispatcher: the
XLA einsum path is the numerics reference and the fallback for platforms where
the Mosaic kernel is unavailable; the flash path is the production TPU kernel.

Shapes follow the JAX convention: q [B, S, Hq, D], k/v [B, S, Hkv, D] with
grouped-query attention when Hkv < Hq.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .dispatch import note_attention, note_choice
from .flash_attention import check_static_window


def _xla_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool,
    positions: Optional[jnp.ndarray],
    kv_positions: Optional[jnp.ndarray],
    window=None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
    sink: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    groups = hq // hkv
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=jnp.float32))

    qg = q.reshape(b, sq, hkv, groups, d)
    # scores in fp32: softmax in bf16 is numerically unacceptable at long seq
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    scores = scores * scale

    if logit_softcap is not None:  # Gemma-2: tanh cap BEFORE the mask
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap

    if causal:
        if positions is None:
            positions = jnp.arange(sq)[None, :]
        if kv_positions is None:
            kv_positions = jnp.arange(sk)[None, :]
        qp = positions[:, None, None, :, None]
        kp = kv_positions[:, None, None, None, :]
        mask = qp >= kp
        if window is not None:  # HF sliding_window band: 0 <= i - j < window
            mask &= (qp - kp) < window
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)

    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:   # one more column a query head holding its sink logit: it takes
        # probability and gives no value (MiMo-V2's window layers)
        column = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, hkv, groups, 1, 1),
            scores.shape[:-1] + (1,))
        probs = jax.nn.softmax(jnp.concatenate([scores, column], -1),
                               axis=-1)[..., :-1]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    # tagged so REMAT_POLICIES["attn"] can keep the [B,S,H,D] output: layers
    # downstream then never re-run this attention forward. (This path's own
    # backward still rebuilds scores/probs — the [S,S] recompute is only
    # fully eliminated on the flash path, whose lse residual is also tagged.)
    return checkpoint_name(
        out.reshape(b, sq, hq, v.shape[-1]).astype(q.dtype), "attn_out")


def resolve_attention_impl(impl: str, q_len: int, kv_len: int, head_dim: int,
                           *, causal: bool = True,
                           standard_layout: bool = True) -> tuple[str, str]:
    """``(impl, reason)`` for one attention shape. A named impl is the
    user's choice and comes back unchanged (an ineligible shape then raises
    in the kernel); ``"auto"`` picks flash on a TPU backend when the call is
    causal, tile-aligned and in the standard contiguous position layout,
    and the einsum reference otherwise — and says which and why."""
    if impl != "auto":
        return impl, "forced"
    backend = jax.default_backend()
    if backend != "tpu":
        return "xla", f"auto: backend is {backend}, not tpu"
    if q_len % 128 or kv_len % 128 or head_dim % 64:
        return "xla", (f"auto: seq {q_len}/{kv_len} % 128 or head_dim "
                       f"{head_dim} % 64 is not tile-aligned")
    if not (causal and standard_layout):
        return "xla", "auto: non-causal or non-contiguous position layout"
    return "flash", "auto: tpu backend, causal, tile-aligned"


def multihead_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    positions: Optional[jnp.ndarray] = None,
    kv_positions: Optional[jnp.ndarray] = None,
    impl: str = "auto",
    standard_layout: bool = True,
    window=None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
    sink: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Scaled-dot-product attention with GQA.

    impl: "xla" (einsum reference), "flash" (Pallas kernel), or "auto"
    (flash on TPU when causal, tile-aligned, and the caller confirms the
    standard contiguous position layout via ``standard_layout`` — sequence-
    sharded/CP callers pass False and get the mask-aware xla path).
    ``window``: sliding-window attention, on both paths. Static ints bake
    the band into the flash kernel; a TRACED window (per-layer patterns,
    Gemma-2) rides the kernel's dynamic band operand — either way
    out-of-band kv tiles are skipped for an O(S*window) cost.
    ``scale``: score scale override (Gemma-2's query_pre_attn_scalar**-0.5;
    default head_dim**-0.5). ``logit_softcap``: Gemma-2 tanh capping of the
    scaled scores — both paths, with the (1 - tanh^2) backward term on the
    flash path. ``sink`` [Hq] (the einsum path alone): a learned logit a
    query head that joins the softmax as one more column with no value.
    The value heads may be narrower than the key heads (``v [.., Dv]``): the
    einsum path returns ``[B, S, Hq, Dv]``.
    """
    if sink is not None and impl != "xla":
        raise ValueError("a sink logit is implemented on the einsum path "
                         "(impl='xla') and in the paged kernel only")
    if window is not None and not causal:
        # the band is defined relative to the causal diagonal; the xla path
        # builds its window mask inside the `if causal:` block and would
        # otherwise silently IGNORE the window (the flash kernel raises) —
        # both paths must fail loudly on this combination
        raise ValueError(
            "window (sliding-window attention) requires causal=True — a "
            "non-causal banded mask is not implemented on either path")
    check_static_window(window)
    reason = "forced"
    if impl == "auto":
        impl, reason = resolve_attention_impl(
            impl, q.shape[1], k.shape[1], q.shape[-1], causal=causal,
            standard_layout=standard_layout)
        note_choice("multihead_attention", impl, reason)
    if impl == "flash":
        from .flash_attention import describe_walk, flash_attention

        note_attention(impl, f"{reason}; "
                       f"{describe_walk(q, k, causal, window)}")
        return flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, logit_softcap=logit_softcap)
    note_attention(impl, reason)
    return _xla_attention(q, k, v, causal, positions, kv_positions, window,
                          scale, logit_softcap, sink)
