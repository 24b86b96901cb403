"""Rotary position embeddings (half-rotation convention, Llama-style).

The reference consumes RoPE through HF ``LlamaRotaryEmbedding`` (it only has to
shim its ``reset_parameters``, ``04-fully-sharded-data-parallel/train_llm.py:32-44``),
which means it inherits every ``rope_scaling`` flavor HF implements — and the
405B chapter's target checkpoint (Llama-3.1,
``05-training-llama-405b/train_llm.py:74-146``) *requires* the ``llama3``
frequency rescale for correct numerics. This module implements the same six
rope types HF's ``ROPE_INIT_FUNCTIONS`` dispatches on (default / linear /
dynamic NTK / yarn / longrope / llama3), as pure functions of the config dict.

Here RoPE is a pure function: compute cos/sin from explicit ``positions`` — the
explicit-positions requirement is load-bearing for sequence parallelism, where
each shard sees a slice of the sequence (reference passes explicit
``position_ids`` for the same reason, ``06-tensor-parallel/train_llm.py:210-212``).

Seq-length-dependent flavors (``dynamic``, ``longrope``'s short/long switch)
use ``max(positions) + 1`` — a *traced* scalar, so the compiled program handles
any batch, exactly like HF's ``@dynamic_rope_update`` recomputing from
``position_ids.max() + 1``. Under context parallelism this max is computed in
GSPMD-land OUTSIDE the attention shard_maps: ``positions`` is one global
(cp-sharded) array, so XLA lowers the reduction as a cp-collective max and
every sequence shard derives the SAME frequencies — no rejection needed
(pinned by the dynamic-rope cp parity test in tests/test_rope_scaling.py).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax.numpy as jnp

ROPE_TYPES = ("default", "linear", "dynamic", "yarn", "longrope", "llama3")

# rope types whose frequencies depend on the runtime sequence length (traced
# from positions via a global max — a cp-collective under sequence sharding,
# see module docstring); everything else is static at trace time
SEQ_DEPENDENT_ROPE_TYPES = ("dynamic", "longrope")


def freeze_rope_scaling(scaling: Optional[dict]) -> Optional[tuple]:
    """HF ``rope_scaling`` dict -> hashable canonical form (sorted item
    tuple, list values tupled) so it can live on the frozen model configs."""
    if scaling is None or isinstance(scaling, tuple):
        return scaling

    def _freeze(v):
        return tuple(v) if isinstance(v, (list, tuple)) else v

    return tuple(sorted((k, _freeze(v)) for k, v in scaling.items()))


def _scaling_dict(scaling) -> dict:
    if isinstance(scaling, dict):
        return scaling
    return dict(scaling)


def rope_type_of(scaling) -> str:
    if not scaling:
        return "default"
    s = _scaling_dict(scaling)
    # "rope_type" is the current HF key; "type" the pre-4.43 one
    return s.get("rope_type") or s.get("type") or "default"


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2], float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def _llama3_frequencies(inv_freq: jnp.ndarray, s: dict) -> jnp.ndarray:
    """Llama-3.1 band-wise rescale: long wavelengths (past the original
    context) compressed by ``factor``, short ones untouched, a smooth
    interpolation between (HF ``_compute_llama3_parameters``)."""
    factor = s["factor"]
    low_freq_factor = s.get("low_freq_factor", 1.0)
    high_freq_factor = s.get("high_freq_factor", 4.0)
    old_context = s["original_max_position_embeddings"]

    low_freq_wavelen = old_context / low_freq_factor
    high_freq_wavelen = old_context / high_freq_factor
    wavelen = 2 * math.pi / inv_freq
    scaled = jnp.where(wavelen > low_freq_wavelen, inv_freq / factor, inv_freq)
    smooth = ((old_context / wavelen - low_freq_factor)
              / (high_freq_factor - low_freq_factor))
    smoothed = (1 - smooth) * scaled / factor + smooth * scaled
    is_medium = (wavelen <= low_freq_wavelen) & (wavelen >= high_freq_wavelen)
    return jnp.where(is_medium, smoothed, scaled)


def _yarn_frequencies(head_dim: int, theta: float, s: dict,
                      max_position: int) -> tuple[jnp.ndarray, float]:
    """YaRN: interpolate-vs-extrapolate per frequency band with a linear ramp
    between correction dims, plus the sqrt-log attention temperature (HF
    ``_compute_yarn_parameters``)."""
    factor = s["factor"]
    # original_max bounds the correction range only; ``factor`` stays the
    # dict's value (matches transformers' _compute_yarn_parameters)
    original_max = s.get("original_max_position_embeddings") or max_position

    def get_mscale(scale, mscale=1.0):
        if scale <= 1:
            return 1.0
        return 0.1 * mscale * math.log(scale) + 1.0

    attention_factor = s.get("attention_factor")
    if attention_factor is None:
        mscale, mscale_all = s.get("mscale"), s.get("mscale_all_dim")
        if mscale and mscale_all:  # deepseek-style split temperature
            attention_factor = get_mscale(factor, mscale) / get_mscale(
                factor, mscale_all)
        else:
            attention_factor = get_mscale(factor)

    beta_fast = s.get("beta_fast") or 32
    beta_slow = s.get("beta_slow") or 1

    def correction_dim(num_rotations):
        return (head_dim * math.log(original_max / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(theta))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if s.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001  # avoid 0/0 on degenerate ranges (HF does the same)

    pos_freqs = theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                          / head_dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low),
        0, 1)
    extrapolation_factor = 1 - ramp
    inv_freq = (interpolation * (1 - extrapolation_factor)
                + extrapolation * extrapolation_factor)
    return inv_freq, float(attention_factor)


def _longrope_frequencies(head_dim: int, theta: float, s: dict,
                          max_position: int, seq_len) -> tuple[jnp.ndarray, float]:
    """Phi-3 longrope: per-dim rescale factors, the *short* set within the
    original context and the *long* set beyond it (seq-dependent, traced),
    with a sqrt-log attention temperature (HF ``_compute_longrope_parameters``)."""
    short = jnp.asarray(s["short_factor"], jnp.float32)
    long = jnp.asarray(s["long_factor"], jnp.float32)
    original_max = s.get("original_max_position_embeddings")
    if original_max:  # Phi-3 style: the max/original ratio overrides factor
        factor = max_position / original_max
    else:
        original_max = max_position
        factor = s.get("factor") or 1.0

    attention_factor = s.get("attention_factor")
    if attention_factor is None:
        if factor <= 1.0:
            attention_factor = 1.0
        else:
            attention_factor = math.sqrt(
                1 + math.log(factor) / math.log(original_max))

    base = theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    ext = jnp.where(seq_len > original_max, long, short)
    return 1.0 / (ext * base), float(attention_factor)


def scaled_rope_frequencies(
    head_dim: int,
    theta: float,
    scaling: Any = None,
    max_position: Optional[int] = None,
    seq_len=None,
) -> tuple[jnp.ndarray, float]:
    """(inv_freq [head_dim//2], attention_factor) for any HF rope type.

    ``scaling`` is the HF ``rope_scaling`` dict (or its frozen-tuple form);
    ``max_position`` the config's max_position_embeddings; ``seq_len`` a
    (possibly traced) current-sequence length, required by the
    seq-dependent types (``dynamic``, ``longrope``)."""
    rope_type = rope_type_of(scaling)
    if rope_type == "default":
        return rope_frequencies(head_dim, theta), 1.0
    s = _scaling_dict(scaling)
    if rope_type == "linear":
        return rope_frequencies(head_dim, theta) / s["factor"], 1.0
    if rope_type == "llama3":
        return _llama3_frequencies(rope_frequencies(head_dim, theta), s), 1.0
    if rope_type == "yarn":
        return _yarn_frequencies(head_dim, theta, s, max_position)
    if rope_type == "dynamic":
        # NTK-by-parts via theta rescale, pivoting at max_position (HF
        # semantics: scaling engages only past the configured context)
        factor = s["factor"]
        if seq_len is None:
            seq_len = max_position
        seq_len = jnp.maximum(jnp.asarray(seq_len, jnp.float32),
                              float(max_position))
        base = theta * ((factor * seq_len / max_position) - (factor - 1)) ** (
            head_dim / (head_dim - 2))
        exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
        return 1.0 / (base ** exponent), 1.0
    if rope_type == "longrope":
        if seq_len is None:
            seq_len = max_position
        return _longrope_frequencies(head_dim, theta, s, max_position, seq_len)
    raise ValueError(
        f"unsupported rope_scaling type {rope_type!r} (supported: {ROPE_TYPES})")


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0, scaling: Any = None,
               max_position: Optional[int] = None,
               interleave: bool = False) -> jnp.ndarray:
    """Rotate ``x`` [..., seq, heads, head_dim] by position-dependent angles.

    ``positions`` is [..., seq] (int). Computation in float32, result cast back
    to ``x.dtype`` — rope in bf16 loses position resolution at long context.
    ``scaling``/``max_position`` select an HF rope_scaling flavor (None =
    plain RoPE, the fast path). ``interleave`` pairs ADJACENT elements
    ``(2i, 2i+1)`` under angle i (the DeepSeek-V2/V3 ``rope_interleave``
    convention; each pair is rotated where it lies) instead of the halves
    ``(i, i + head_dim/2)``."""
    head_dim = x.shape[-1]
    if scaling is None:
        inv_freq, attn_factor = rope_frequencies(head_dim, theta), 1.0
    else:
        seq_len = None
        if rope_type_of(scaling) in SEQ_DEPENDENT_ROPE_TYPES:
            seq_len = jnp.max(positions) + 1  # traced, like HF's position_ids.max()
        inv_freq, attn_factor = scaled_rope_frequencies(
            head_dim, theta, scaling, max_position, seq_len)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :] * attn_factor  # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :] * attn_factor
    xf = x.astype(jnp.float32)
    if interleave:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        rotated = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                            axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                                  axis=-1)
    return rotated.astype(x.dtype)


def position_query_scale(positions: jnp.ndarray, beta: float,
                         original_max: int) -> jnp.ndarray:
    """``1 + beta * ln(1 + floor(t / original_max))``, float32: the
    position-dependent query scale of ``llama_4_scaling_beta`` models
    (1 inside the original context, growing by ``beta`` per e-fold of
    context beyond it). Applied to a query at its OWN position."""
    blocks = jnp.floor_divide(positions, original_max).astype(jnp.float32)
    return 1.0 + beta * jnp.log1p(blocks)
