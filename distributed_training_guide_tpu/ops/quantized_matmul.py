"""Block-dequant matmul for int8 serve-plane weights.

The serving engine stores its largest params as ``train/precision.py``
``Quantized`` leaves — int8 payload plus per-block fp32 absmax scales over
the TRAILING axis (Dettmers, arXiv:2110.02861) — and this module is the one
place the dequant happens: fused into the matmul's block loop, one
``[K, block]`` fp32 transient at a time, so the full fp32 weight tensor
never materializes in the lowered program (the engine HLO pins assert
this, like the int8 kv-pool aval pins).

Two contraction forms:

- **standard** (``x [.., K] @ w [K, N]``, blocks tile N): the scale of a
  weight column depends on its (row, column-block), so it cannot factor
  out of the contraction over K — each column block is dequantized to a
  ``[K, bs]`` fp32 transient immediately before its ``[M, K] @ [K, bs]``
  partial matmul.
- **transpose** (``x [.., E] @ w[V, E].T``, blocks tile E — the tied
  lm_head): here the block IS a slice of the contraction axis, so the
  scale factors out per block: ``out += (x[:, blk] @ q[:, blk].T) *
  scale[:, b]`` with an ``[M, V]`` fp32 accumulator (that accumulator is
  the logits — activation-sized, not weight-sized).

The XLA reference walks blocks with ``lax.scan`` (compact while-loop HLO,
works for real-model block counts; it is the gather-form CPU-parity
reference, the same role the gather attend plays for the paged flash
kernel). The Pallas kernel maps one grid step per block with the scale
column riding the same BlockSpec index — the int8-KV scale-prefetch
pattern from ``ops/paged_decode.py`` — and runs in interpret mode on CPU
CI. Dispatch mirrors ``paged_decode``: ``impl="auto"`` lowers to Pallas
only on a TPU backend when the tile geometry is eligible.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import lane_column, note_choice, resolve_interpret

__all__ = ["quantized_matmul", "quantized_matmul_eligible", "quantized_take"]


def _geometry(q: jax.Array, scale: jax.Array) -> tuple[int, int]:
    """(nblocks, block_size) from the container shapes — the recovery rule
    guaranteed by ``train/precision.py``'s ``block_geometry``."""
    d, nb = q.shape[-1], scale.shape[-1]
    return nb, -(-d // nb)


def _check(w) -> tuple[jax.Array, jax.Array]:
    q, scale = w.q, w.scale
    if getattr(w, "sqrt_domain", False):
        raise ValueError("quantized_matmul expects linear-domain weights; "
                         "sqrt_domain containers are an optimizer-moment "
                         "encoding (train/precision.py)")
    if q.ndim != 2:
        raise ValueError(f"quantized_matmul takes a 2-D weight, got "
                         f"q.shape={q.shape} (slice the layer scan axis "
                         f"before calling)")
    return q, scale


# ---------------------------------------------------------------------------
# XLA reference (the CPU-parity path; also the tp path under GSPMD)
# ---------------------------------------------------------------------------

def _matmul_xla(x2d: jax.Array, q: jax.Array, scale: jax.Array) -> jax.Array:
    """standard form: [M, K] @ dequant([K, N]) -> [M, N] fp32."""
    k, n = q.shape
    nb, bs = _geometry(q, scale)
    pad = nb * bs - n
    if pad:  # int8 zero columns dequantize to 0.0 — harmless, sliced off
        q = jnp.pad(q, ((0, 0), (0, pad)))
    qb = q.reshape(k, nb, bs).transpose(1, 0, 2)          # [nb, K, bs] int8
    sb = scale.T[:, :, None]                              # [nb, K, 1]  fp32
    xf = x2d.astype(jnp.float32)

    def step(_, inp):
        qblk, sblk = inp
        wblk = qblk.astype(jnp.float32) * sblk            # [K, bs] transient
        return None, xf @ wblk

    _, ys = jax.lax.scan(step, None, (qb, sb))            # [nb, M, bs]
    out = ys.transpose(1, 0, 2).reshape(x2d.shape[0], nb * bs)
    return out[:, :n] if pad else out


def _matmul_t_xla(x2d: jax.Array, q: jax.Array, scale: jax.Array) -> jax.Array:
    """transpose form: [M, E] @ dequant([V, E]).T -> [M, V] fp32."""
    v, e = q.shape
    nb, bs = _geometry(q, scale)
    pad = nb * bs - e
    xf = x2d.astype(jnp.float32)
    if pad:  # zero-padded activations meet zero-padded weights: no-op terms
        q = jnp.pad(q, ((0, 0), (0, pad)))
        xf = jnp.pad(xf, ((0, 0), (0, pad)))
    qb = q.reshape(v, nb, bs).transpose(1, 0, 2)          # [nb, V, bs] int8
    xb = xf.reshape(-1, nb, bs).transpose(1, 0, 2)        # [nb, M, bs] fp32
    sb = scale.T                                          # [nb, V]    fp32

    def step(acc, inp):
        qblk, xblk, sblk = inp
        # scale is a function of the contracted block here, so it factors
        # out of the per-block partial product
        return acc + (xblk @ qblk.astype(jnp.float32).T) * sblk[None, :], None

    acc0 = jnp.zeros((x2d.shape[0], v), jnp.float32)
    out, _ = jax.lax.scan(step, acc0, (qb, xb, sb))
    return out


# ---------------------------------------------------------------------------
# Pallas kernel (standard form): one grid step per weight block, the scale
# column prefetched by the same BlockSpec index as its int8 payload block
# ---------------------------------------------------------------------------

def _dequant_matmul_kernel(x_ref, q_ref, s_ref, o_ref):
    # the [K, nb] scale table rides whole; block b's column is picked out
    col = lane_column(s_ref[...], pl.program_id(0))       # [K, 1]
    wblk = q_ref[...].astype(jnp.float32) * col           # [K, bs] in VMEM
    o_ref[...] = jnp.dot(x_ref[...].astype(jnp.float32), wblk,
                         preferred_element_type=jnp.float32)


def _matmul_pallas(x2d: jax.Array, q: jax.Array, scale: jax.Array,
                   interpret: bool) -> jax.Array:
    m, k = x2d.shape
    n = q.shape[-1]
    nb, bs = _geometry(q, scale)
    return pl.pallas_call(
        _dequant_matmul_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((m, k), lambda b: (0, 0)),       # whole activations
            pl.BlockSpec((k, bs), lambda b: (0, b)),      # int8 block b
            pl.BlockSpec((k, nb), lambda b: (0, 0)),      # all scale columns
        ],
        out_specs=pl.BlockSpec((m, bs), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="qmm",
    )(x2d, q, scale)


def quantized_matmul_eligible(w, *, transpose: bool = False) -> bool:
    """True when the Pallas kernel's tile geometry fits this container:
    no padded tail block, lane-dim blocks (bs % 128), and an int8-tileable
    contraction dim (K % 32 — the int8 min tile is (32, 128) per the TPU
    guide). The transpose form has no kernel yet — XLA carries it."""
    try:
        q, scale = _check(w)
    except ValueError:
        return False
    if transpose:
        return False
    k, n = q.shape
    nb, bs = _geometry(q, scale)
    return nb * bs == n and bs % 128 == 0 and k % 32 == 0


def quantized_take(w, ids: jax.Array) -> jax.Array:
    """Embedding lookup against a quantized table: gather int8 rows and
    their scale rows, dequantize only the gathered tokens (fp32 out)."""
    q, scale = _check(w)
    nb, bs = _geometry(q, scale)
    rows = jnp.take(q, ids, axis=0).astype(jnp.float32)       # [.., d]
    srows = jnp.take(scale, ids, axis=0)                      # [.., nb]
    srows = jnp.repeat(srows, bs, axis=-1)[..., :q.shape[-1]]
    return rows * srows


def quantized_matmul(x: jax.Array, w, *, transpose: bool = False,
                     impl: str = "auto",
                     interpret: Optional[bool] = None) -> jax.Array:
    """``x @ dequant(w)`` (or ``x @ dequant(w).T`` with ``transpose``),
    block-dequantizing inside the contraction loop. Returns fp32 (callers
    cast to compute dtype; the lm_head keeps fp32 logits).

    ``w`` is any Quantized-like container with ``.q`` (int8, blocks on the
    trailing axis) and ``.scale`` (fp32) — duck-typed so the model family
    modules need not import ``train.precision`` (train imports models).
    """
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"impl must be auto|xla|pallas, got {impl!r}")
    q, scale = _check(w)
    lead, kdim = x.shape[:-1], x.shape[-1]
    contract = q.shape[-1] if transpose else q.shape[0]
    if kdim != contract:
        raise ValueError(f"contraction mismatch: x[.., {kdim}] vs "
                         f"quantized weight {q.shape}"
                         f"{'.T' if transpose else ''}")
    x2d = x.reshape(-1, kdim)
    eligible = quantized_matmul_eligible(w, transpose=transpose)
    if impl == "auto":
        use_pallas = jax.default_backend() == "tpu" and eligible
        note_choice(
            "quantized_matmul", "pallas" if use_pallas else "xla",
            f"auto: backend is {jax.default_backend()}, weight "
            f"{tuple(q.shape)}{'.T' if transpose else ''} in "
            f"{_geometry(q, scale)[1]}-wide blocks is "
            f"{'' if eligible else 'not '}a shape the kernel takes")
    else:
        use_pallas = impl == "pallas"
    if use_pallas:
        if transpose:
            raise NotImplementedError("pallas quantized_matmul has no "
                                      "transpose (tied lm_head) form; use "
                                      "impl='xla'")
        interpret = resolve_interpret(interpret)
        if not interpret and not eligible:
            raise ValueError(
                f"quantized_matmul impl='pallas' (compiled) needs whole "
                f"blocks, block width % 128 == 0 and K % 32 == 0; got "
                f"weight {tuple(q.shape)} in "
                f"{_geometry(q, scale)[1]}-wide blocks — use impl='xla'")
        out = _matmul_pallas(x2d, q, scale, interpret)
    elif transpose:
        out = _matmul_t_xla(x2d, q, scale)
    else:
        out = _matmul_xla(x2d, q, scale)
    return out.reshape(*lead, out.shape[-1])
