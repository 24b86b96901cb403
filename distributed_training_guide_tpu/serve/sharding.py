"""Serve-side sharding: the page pool partitioned over the mesh.

Training shards parameters through ``parallel/plans.py``'s logical-axis
rules; serving state (KV page pools, block tables, lengths, sampling
knobs) has no logical-axis annotations — it is a handful of engine-owned
arrays with stable names. The mechanism here is therefore the
``match_partition_rules`` pattern (regex over tree paths -> PartitionSpec,
the standard JAX-LLM idiom): one rules table says where every piece of
serve state lives on the mesh, and everything not matched fails loudly
instead of silently replicating.

The layout itself mirrors the attention plans in ``parallel/plans.py``:
under tp the q/k/v projections shard on (kv-)heads, so the page pool
``[L, n_pages, page, kvh, hd]`` splits on the SAME kv-head axis — each
chip holds ``kvh/tp`` heads' worth of every page, block tables and
lengths are replicated (they are tiny int32 bookkeeping), and attention
is embarrassingly parallel over heads. The attend (scatter new k/v +
paged flash-decode kernel / gather reference) runs under a FULL-MANUAL
``shard_map``: each chip scatters into and reads from its own pool slice,
no collective appears inside the region, and the only cross-chip traffic
of a decode step is what GSPMD inserts around it anyway (the out
projection's row-parallel psum and the vocab-sharded sampling psums).
Full-manual (every mesh axis) rather than partial-auto because the
chip's lowering refuses a Pallas call in a region that leaves a mesh axis
auto ("Mosaic kernels cannot be automatically partitioned") — which also
means the serve mesh must have tp as its only non-trivial axis
(``validate_kv_shard``).

The Mosaic kernel is the forcing function: GSPMD cannot partition a
``pallas_call``, so without the manual region a sharded engine ran the
kernel replicated with a replicated pool. With it, the kernel body is
unchanged — a per-chip pool slice is just a smaller pool — and the
region is T-agnostic: the decode step (T=1), the speculative verify
forward (T=k+1), and a prefill chunk all run the same block_q=T kernel
per chip through this one attend wrapper.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .kv_pages import copy_pages, num_kv_heads, paged_attend

# Regex -> PartitionSpec over serve-state tree paths. The pool splits on
# the kv-head axis (dim 3 of [L, n_pages, page, kvh, hd]); every host-side
# bookkeeping array the compiled programs consume is replicated. An
# unmatched leaf is an error by design (silent replication of a pool-sized
# tensor is the exact failure class this table exists to prevent).
# A QUANTIZED pool (serve/kv_pages.py kv_dtype="int8") is a Quantized
# NamedTuple per pool: int8 payload [L, P, page, kvh, hd] plus fp32 scales
# [L, P, page, kvh, 1] — BOTH split on the same kv-head axis (each chip's
# heads dequantize with each chip's scales, so the manual attend and
# copy regions stay collective-free; the per-(position, head) scale grain
# is what makes that possible — a cross-head block would need a gather).
SERVE_KV_RULES = (
    (r"pages/(k|v)(/(q|scale))?$", P(None, None, None, "tp", None)),
    (r"(tables|table_row)$", P()),
    (r"(lengths|tokens|seeds|actives|n_valid)$", P()),
    (r"(temps|top_ks|top_ps)$", P()),
)

# specs for the shard_map'd regions: activations [S, T, H, D] split on
# heads, the stacked pools [L, P, page, kvh, hd] split on kv-heads (the
# attend takes them whole, with the layer's index)
_HEADS = P(None, None, "tp", None)
_POOL_L = P(None, None, None, "tp", None)


def match_partition_rules(rules, tree):
    """PartitionSpec pytree for ``tree``: each leaf's '/'-joined path is
    matched against ``rules`` (ordered (regex, spec) pairs, first hit
    wins); scalar/size-1 leaves replicate, anything unmatched raises."""

    def name_of(path) -> str:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            elif hasattr(p, "name"):       # NamedTuple fields (GetAttrKey):
                parts.append(str(p.name))  # the Quantized pool's q/scale
            else:
                parts.append(str(p))
        return "/".join(parts)

    def spec_for(path, leaf):
        name = name_of(path)
        shape = np.shape(leaf)
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()
        for rule, spec in rules:
            if re.search(rule, name):
                return spec
        raise ValueError(f"no serve partition rule matches leaf {name!r} "
                         f"(shape {shape})")

    return jax.tree_util.tree_map_with_path(spec_for, tree)


def serve_kv_shardings(mesh: Mesh, tree):
    """NamedSharding pytree for serve state under ``SERVE_KV_RULES``."""
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        match_partition_rules(SERVE_KV_RULES, tree),
                        is_leaf=lambda x: isinstance(x, P))


def validate_kv_shard(plan, config) -> None:
    """The sharded-pool contract: tp is the mesh's only non-trivial axis
    (the attend region is full-manual — see module docstring) and tp
    divides both head counts so every chip owns whole (kv-)heads."""
    if plan is None:
        raise ValueError("shard_kv=True needs a plan= with a tp mesh "
                         "(parallel.make_plan('tp', make_mesh(tp=N)))")
    mesh = plan.mesh
    tp = int(mesh.shape["tp"])
    if tp < 2:
        raise ValueError(f"shard_kv=True needs mesh tp > 1, got tp={tp}")
    extra = [a for a in plan.active_axes() if a != "tp"]
    if extra:
        raise ValueError(
            f"shard_kv supports tp-only meshes (the attend region is "
            f"full-manual over every axis); axes {extra} have size > 1")
    kvh, hq = num_kv_heads(config), config.num_heads
    if kvh % tp or hq % tp:
        raise ValueError(
            f"kv pool shards on the kv-head axis: num_kv_heads ({kvh}) and "
            f"num_heads ({hq}) must both divide by tp ({tp})")


def _manual(mesh: Mesh):
    return set(mesh.axis_names)


def make_sharded_attend(mesh: Mesh, tables, lengths, *, impl: str = "auto",
                        n_valid=None):
    """The shard_map'd twin of ``kv_pages.make_attend`` (the same contract:
    the stacked pools and the layer's index): per-chip pool slices and head
    groups, replicated tables/lengths/layer, no collective in the region
    (head-parallel attention needs none — the psums of a sharded decode
    step live in GSPMD's out-projection/sampling land). ``layer`` and a
    traced per-layer ``window`` (Gemma-2 schedules) ride as explicit
    replicated operands — shard_map must not close over tracers."""

    def attend(q, k_new, v_new, k_pages, v_pages, layer, *, window=None,
               scale=None, softcap=None):
        operands = [q, k_new, v_new, k_pages, v_pages, layer, tables,
                    lengths]
        in_specs = [_HEADS, _HEADS, _HEADS, _POOL_L, _POOL_L, P(), P(), P()]
        if n_valid is not None:
            operands.append(n_valid)
            in_specs.append(P())
        dyn_window = window is not None and not isinstance(window, int)
        if dyn_window:
            operands.append(window)
            in_specs.append(P())

        def body(q, kn, vn, kp, vp, i, tab, lens, *rest):
            rest = list(rest)
            nv = rest.pop(0) if n_valid is not None else None
            w = rest.pop(0) if dyn_window else window
            return paged_attend(q, kn, vn, kp, vp, i, tab, lens, window=w,
                                scale=scale, softcap=softcap, impl=impl,
                                n_valid=nv)

        sm = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=(_HEADS, (_POOL_L, _POOL_L)),
                           axis_names=_manual(mesh), check_vma=False)
        return sm(*operands)

    return attend


def make_sharded_copy(mesh: Mesh):
    """shard_map'd ``copy_pages`` (CoW fork): each chip copies its slice
    of the source page — page ids are replicated scalars."""

    def copy(pools, src, dst):
        spec = {"k": _POOL_L, "v": _POOL_L}
        sm = jax.shard_map(
            copy_pages, mesh=mesh, in_specs=(spec, P(), P()),
            out_specs=spec, axis_names=_manual(mesh), check_vma=False)
        return sm(pools, src, dst)

    return copy
