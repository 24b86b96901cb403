"""Causal-LM loss.

The reference relies on HF's internal loss (labels = input_ids, shift done by
the model — see data pipeline ``01-single-gpu/train_llm.py:234`` where
``labels = input_ids.copy()``). Here the shift lives in the loss so the model
stays a pure logits function. Log-softmax is computed in float32.

Padding/ignored positions use the HF convention: ``label == -100`` masks the
position out of the mean.
"""
from __future__ import annotations

import jax.numpy as jnp
import jax

IGNORE_INDEX = -100


def causal_lm_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token cross-entropy.

    logits: [B, S, V]; labels: [B, S] (same tokens as inputs, shifted here).
    """
    logits = logits[:, :-1, :].astype(jnp.float32)
    targets = labels[:, 1:]
    valid = targets != IGNORE_INDEX
    safe_targets = jnp.where(valid, targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe_targets[..., None], axis=-1)[..., 0]
    nll = (logz - picked) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def chunked_nll_sums(hidden: jnp.ndarray, w_out: jnp.ndarray,
                     labels: jnp.ndarray, num_chunks: int = 8,
                     logits_sharding=None) -> tuple:
    """``(nll_sum, count)`` of the next-token cross-entropy straight from the
    final hidden states, never materializing full [B, S, V] logits.

    The fp32 logits (+ their cotangent) are the activation-memory limiter for
    big-vocab models — llama-3 at V=128k, B=8, S=2048 is ~8.4 GB just for
    logits. Here the (shifted) sequence is processed in ``num_chunks`` scanned
    slices: each slice computes its own logits [B, S/chunks, V], reduces to
    (nll_sum, count) and drops them; ``jax.checkpoint`` on the body makes the
    backward recompute each slice's logits too, so peak memory falls by
    ~num_chunks at the cost of one extra lm_head matmul pass.

    hidden: [B, S, E]; w_out: [E, V]; labels: [B, S]. The product takes
    ``w_out`` in ``hidden``'s dtype, cast inside the scanned body: a wider
    ``w_out`` is what the scan closes over, so its cotangent, summed over the
    chunks by the scan's transpose, accumulates that wide.
    """
    b, s, e = hidden.shape
    h = hidden[:, :-1, :]
    targets = labels[:, 1:]
    n = s - 1
    # pad to a multiple of num_chunks with ignored positions
    pad = (-n) % num_chunks
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)),
                          constant_values=IGNORE_INDEX)
    chunk = (n + pad) // num_chunks
    h = h.reshape(b, num_chunks, chunk, e).transpose(1, 0, 2, 3)      # [C,B,c,E]
    targets = targets.reshape(b, num_chunks, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, xs):
        nll_sum, count = carry
        h_c, t_c = xs
        logits = jnp.einsum("bce,ev->bcv", h_c, w_out.astype(h_c.dtype),
                            preferred_element_type=jnp.float32)
        if logits_sharding is not None:  # loss-parallel: vocab stays sharded
            logits = jax.lax.with_sharding_constraint(logits, logits_sharding)
        valid = t_c != IGNORE_INDEX
        safe = jnp.where(valid, t_c, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = (logz - picked) * valid
        return (nll_sum + nll.sum(), count + valid.sum()), None

    (nll_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (h, targets))
    return nll_sum, count


def chunked_causal_lm_loss(hidden: jnp.ndarray, w_out: jnp.ndarray,
                           labels: jnp.ndarray, num_chunks: int = 8,
                           logits_sharding=None) -> jnp.ndarray:
    """Mean of ``chunked_nll_sums`` over the positions that count."""
    nll_sum, count = chunked_nll_sums(hidden, w_out, labels, num_chunks,
                                      logits_sharding)
    return nll_sum / jnp.maximum(count, 1)


@jax.custom_vjp
def _cotangents_together(hidden, w_shard):
    """Identity whose backward hands both cotangents on at once: the layers'
    backward, which waits for ``hidden``'s, then also waits for the matrix's,
    so the one reduce-scatter runs where the gradient is whole and the
    whole-matrix partial sum is not held through the layers' backward (left
    alone the scheduler puts the reduce-scatter after it)."""
    return hidden, w_shard


_cotangents_together.defvjp(
    lambda hidden, w_shard: ((hidden, w_shard), None),
    lambda _, cts: jax.lax.optimization_barrier(cts))


def make_gathered_chunked_loss(mesh, w_spec, data_axes, *, num_chunks: int):
    """``chunked_causal_lm_loss`` for an output matrix that is SHARDED over
    data axes (FSDP's lm_head): one gather before the chunk loop, one
    reduce-scatter of the weight gradient after it.

    Left to GSPMD, the scan over chunks closes over the sharded matrix and
    the partitioner gathers it where it is used (once a chunk, forward and
    rematted backward) and reduce-scatters its cotangent where that is made
    (once a chunk). Here the loss runs in a region manual over the whole
    mesh: the shard is all-gathered along the dim ``w_spec`` shards, the
    chunk loop runs on this chip's batch rows against the whole matrix, so
    its transpose accumulates a chip-local partial weight gradient with no
    collective in either loop, and the gather's transpose
    (``collectives.gather_with_reduce_scatter_vjp``: reduced in fp32) is the
    one reduce-scatter. The gather hands the loop the matrix WIDENED to
    fp32, so the partial gradient accumulates in fp32 across the chunks and
    meets the reduction unrounded (the per-chunk program rounds its running
    sum to the compute dtype once a chunk); each chunk's product still reads
    the matrix in the compute dtype (``chunked_nll_sums`` casts in its body:
    the compiler hoists the cast out of the loop and the fp32 copy is never
    made, tests/test_chip_compile.py). The matrix's cotangent is summed
    over the mesh axes ``w_spec`` does not name by the region's own
    transpose. The gather is the sub-scope ``head_gather`` (utils/trace.py).

    ``w_spec``: the [E, V] matrix's PartitionSpec, exactly one dim sharded.
    Returns ``loss(hidden [B, S, E], w_shard, labels [B, S]) -> scalar``.
    """
    from jax.sharding import PartitionSpec as P

    from .collectives import gather_with_reduce_scatter_vjp, psum
    from .flash_attention import wrapper_shard_map

    (dim, axes), = [(i, e) for i, e in enumerate(w_spec) if e is not None]
    gather = gather_with_reduce_scatter_vjp(axes, dim)
    batch = tuple(a for a in data_axes if mesh.shape[a] > 1)

    def body(hidden, w_shard, labels):
        hidden, w_shard = _cotangents_together(hidden, w_shard)
        with jax.named_scope("head_gather"):
            w_out = gather(w_shard)
        nll_sum, count = chunked_nll_sums(hidden, w_out, labels, num_chunks)
        return psum(nll_sum, batch) / jnp.maximum(psum(count, batch), 1)

    return wrapper_shard_map(
        mesh, in_specs=(P(batch, None, None), w_spec, P(batch, None)),
        out_specs=P())(body)


def validate_chunked_loss_support(family_mod, family: str, loss_fn) -> None:
    """Common preconditions for the chunked loss (checked by both the plain
    and the pipeline step builders)."""
    if not hasattr(family_mod, "output_weights"):
        raise NotImplementedError(
            f"loss_chunks unsupported for family {family!r}")
    if loss_fn is not causal_lm_loss:
        raise NotImplementedError(
            "loss_chunks hardwires the causal-LM loss; drop the custom "
            "loss_fn or the chunking")
