"""Pallas flash-attention numerics goldens vs the XLA reference path.

Runs the real kernels in interpreter mode on CPU (same code path the TPU
compiles), checking forward and all three gradients, with GQA and both
block-aligned and multi-block shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.ops.attention import _xla_attention
from distributed_training_guide_tpu.ops import flash_attention as fa
from distributed_training_guide_tpu.ops.flash_attention import flash_attention

# output / gradient tolerances against the XLA path by operand dtype: bf16
# operands go to the MXU as stored and p / ds are rounded to them, as
# ``_xla_attention`` rounds its probabilities
TOL = {jnp.float32: (1e-5, 2e-4), jnp.bfloat16: (2e-2, 6e-2)}
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["fp32", "bf16"])


def assert_close(got, want, tol, err_msg=""):
    assert got.dtype == want.dtype, (got.dtype, want.dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol, err_msg=err_msg)


def make_qkv(b, s, hq, hkv, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    return q, k, v


@DTYPES
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("s", [64, 128])
def test_forward_matches_xla(hq, hkv, s, dtype):
    q, k, v = make_qkv(2, s, hq, hkv, 32, dtype)
    ref = _xla_attention(q, k, v, causal=True, positions=None, kv_positions=None)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    assert_close(out, ref, TOL[dtype][0])


def test_noncausal_forward():
    q, k, v = make_qkv(1, 64, 2, 2, 32)
    ref = _xla_attention(q, k, v, causal=False, positions=None, kv_positions=None)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# head_dim 128 takes the kernels' seq-major operands ([B, S, H*D] as the
# model holds them), 32 and 64 the head-major ones behind their transposes
# (``fa._Operands``): every grid below runs both, the seq-major ones also at
# Laguna's 64 / 8 heads (eight query heads read one kv column block)
HEADS_AND_DIMS = [(4, 4, 32), (4, 2, 32), (4, 2, 128), (64, 8, 128),
                  (4, 2, 64)]


@DTYPES
@pytest.mark.parametrize("hq,hkv,d", HEADS_AND_DIMS)
def test_grads_match_xla(hq, hkv, d, dtype):
    q, k, v = make_qkv(1, 64, hq, hkv, d, dtype, seed=1)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            interpret=True).astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = _xla_attention(q, k, v, causal=True, positions=None,
                           kv_positions=None).astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_ref):
        assert_close(a, b, TOL[dtype][1], err_msg=f"d{name}")


def test_uneven_blocks():
    """seq not divisible by preferred block -> picker falls back."""
    q, k, v = make_qkv(1, 96, 2, 2, 32)
    ref = _xla_attention(q, k, v, causal=True, positions=None, kv_positions=None)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_forced_flash_rejects_untiled_shapes():
    """Compiled (non-interpret) flash with tile-indivisible shapes must fail
    loudly, not fall back to a full-sequence block (opaque Mosaic errors)."""
    q, k, v = make_qkv(1, 96, 2, 2, 32)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, causal=True, interpret=False)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_sharded_flash_partitions_instead_of_replicating(eight_devices,
                                                         head_dim):
    """GSPMD's fallback for the Mosaic custom call is gather-and-replicate;
    the shard_map wrapper must instead keep the kernel local: numerics match
    the dense reference AND the output/grad shardings keep their mesh axes
    (a replicated grad spec is exactly the failure being guarded)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_training_guide_tpu.ops.flash_attention import (
        make_sharded_flash_attention)

    from distributed_training_guide_tpu.ops import dispatch

    mesh = Mesh(np.array(eight_devices).reshape(2, 4), ("dp", "tp"))
    q, k, v = make_qkv(4, 128, 8, 4, head_dim, seed=2)
    attn = make_sharded_flash_attention(mesh, batch_axes=("dp",),
                                        head_axis="tp", forced=True)
    with dispatch.record_attention() as record:   # the maps' kernels keep
        attn(q, k, v)                             # head-major operands
    assert record["flash"].endswith(
        "; head-major operands" if head_dim == 128
        else "; head-major (head_dim 64) operands"), record
    sh = NamedSharding(mesh, P("dp", None, "tp", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    @jax.jit
    def f(q, k, v):
        return jax.value_and_grad(
            lambda q: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2))(q)

    loss, grad = f(qs, ks, vs)
    ref = jax.value_and_grad(
        lambda q: jnp.sum(_xla_attention(q, k, v, True, None, None)
                          .astype(jnp.float32) ** 2))(q)
    np.testing.assert_allclose(float(loss), float(ref[0]), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref[1]),
                               rtol=2e-4, atol=2e-4)
    assert grad.sharding.spec == P("dp", None, "tp", None), grad.sharding
    # single-device meshes need no wrapper
    assert make_sharded_flash_attention(
        Mesh(np.array(eight_devices[:1]).reshape(1, 1), ("dp", "tp"))) is None
    # packed/non-contiguous layouts must fail loud (no positions reach the
    # callable, so a silent arange mask would be wrong)
    with pytest.raises(ValueError, match="contiguous"):
        attn(q, k, v, standard_layout=False)
    # batch not divisible by the manual axes: non-forced falls back to the
    # partitionable XLA path instead of crashing in shard_map
    attn_auto = make_sharded_flash_attention(mesh, batch_axes=("dp",),
                                             head_axis="tp", forced=False)
    q3, k3, v3 = make_qkv(3, 128, 8, 4, head_dim, seed=4)
    ref3 = _xla_attention(q3, k3, v3, True, None, None)
    np.testing.assert_allclose(np.asarray(attn_auto(q3, k3, v3)),
                               np.asarray(ref3), rtol=2e-4, atol=2e-4)


def test_trainer_forced_flash_matches_xla_on_sharded_plan(eight_devices):
    """End-to-end: a tp_fsdp train step with attn_impl='flash' (the sharded
    wrapper engages) reproduces the attn_impl='xla' losses."""
    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.train import Trainer, adamw_cosine

    def run(attn_impl):
        bundle = get_model("llama-debug")
        plan = make_plan("tp_fsdp", make_mesh(tp=2, fsdp=2))
        t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3), plan=plan,
                    attn_impl=attn_impl, donate=False)
        state = t.init_state(0)
        ids = np.random.RandomState(3).randint(0, bundle.config.vocab_size,
                                               (4, 128))
        batch = {kk: jax.device_put(jnp.asarray(ids), t.batch_shardings()[kk])
                 for kk in ("input_ids", "labels")}
        losses = []
        for _ in range(3):
            state, m = t.step_fn(state, batch)
            losses.append(float(m["loss"]))
        return losses

    np.testing.assert_allclose(run("flash"), run("xla"), rtol=2e-4)


def test_attn_remat_policy_through_flash_vjp():
    """The "attn" policy's checkpoint_name tags (flash_out / flash_lse,
    tagged inside the kernel's custom_vjp fwd) must survive jax.checkpoint:
    gradients under the policy match the un-remat'd ones. This is the bench
    headline configuration (remat_policy=attn + flash attention)."""
    from distributed_training_guide_tpu.train.step import REMAT_POLICIES

    q, k, v = make_qkv(1, 64, 4, 2, 32)

    def f(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            interpret=True)
        return jnp.sum(o * o)  # nonlinear consumer: backward needs o itself

    ref = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(jax.checkpoint(f, policy=REMAT_POLICIES["attn"]),
                   argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ref, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    # numerics hold under ANY policy, so also pin the mechanism: with the
    # tags saved, backward runs 3 pallas_calls (dq + dkv + one fwd for the
    # primal output) vs 4 under full recompute (fwd re-run for residuals).
    # If a checkpoint_name tag drifts, the policy silently degrades to full
    # recompute and only this count catches it.
    def n_pallas(policy):
        jaxpr = jax.make_jaxpr(
            jax.grad(jax.checkpoint(f, policy=policy)))(q, k, v)
        return str(jaxpr).count("pallas_call")

    saved, recompute = n_pallas(REMAT_POLICIES["attn"]), n_pallas(REMAT_POLICIES["all"])
    assert saved < recompute, (saved, recompute)


def test_attn_remat_policy_through_sharded_wrapper(eight_devices):
    """Same mechanism pin for the SHARDED wrapper (the multi-chip path): the
    attn policy must save the tagged output + lse so backward runs 3 pallas
    calls, not 4. This regressed invisibly before: the fwd shard_map
    returned residual-only outputs (in-map transposes / kernel-layout o),
    and since a shard_map eqn is atomic under jax.checkpoint's partial-eval,
    rebuilding ANY of them re-ran the whole map — kernel included — making
    the policy silent full-recompute on every sharded mesh."""
    from jax.sharding import Mesh

    from distributed_training_guide_tpu.ops.flash_attention import (
        make_sharded_flash_attention)
    from distributed_training_guide_tpu.train.step import REMAT_POLICIES

    mesh = Mesh(np.array(eight_devices).reshape(8, 1), ("dp", "tp"))
    attn = make_sharded_flash_attention(mesh, batch_axes=("dp",),
                                        head_axis=None, forced=True)
    q, k, v = make_qkv(8, 64, 4, 2, 32, seed=7)

    def f(q, k, v):
        o = attn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    ref = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(jax.checkpoint(f, policy=REMAT_POLICIES["attn"]),
                   argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ref, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def n_pallas(policy):
        jaxpr = jax.make_jaxpr(
            jax.grad(jax.checkpoint(f, policy=REMAT_POLICIES[policy])))(q, k, v)
        return str(jaxpr).count("pallas_call")

    assert n_pallas("attn") < n_pallas("all"), \
        (n_pallas("attn"), n_pallas("all"))


# ---------------------------------------------------------------------------
# Gemma-2 attention extras: the {softcap, scale, window, per-layer windows}
# feature grid vs the XLA reference — fwd and all three grads, fp32
# interpret mode, GQA included. One combination per row so a regression
# names the feature that broke.
# ---------------------------------------------------------------------------

EXTRAS_GRID = [
    dict(logit_softcap=50.0),
    dict(scale=24.0 ** -0.5),
    dict(window=24),
    dict(logit_softcap=30.0, scale=24.0 ** -0.5),
    dict(logit_softcap=30.0, window=24),
    dict(logit_softcap=30.0, scale=24.0 ** -0.5, window=24),  # full Gemma-2
]


@pytest.mark.parametrize("extras", EXTRAS_GRID,
                         ids=lambda e: "+".join(sorted(e)))
@pytest.mark.parametrize("hq,hkv,d", HEADS_AND_DIMS)
def test_attention_extras_fwd_and_grads_match_xla(extras, hq, hkv, d):
    from distributed_training_guide_tpu.ops.attention import (
        multihead_attention)

    q, k, v = make_qkv(1, 64, hq, hkv, d, seed=3)

    def loss(attn_fn):
        def f(q, k, v):
            o = attn_fn(q, k, v)
            return jnp.mean(o * jnp.cos(o))
        return f

    def flash_fn(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                               interpret=True, **extras)

    def xla_fn(q, k, v):
        return multihead_attention(q, k, v, causal=True, impl="xla", **extras)

    out = flash_fn(q, k, v)
    ref = xla_fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    g_flash = jax.grad(loss(flash_fn), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(xla_fn), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=f"d{name}")


def test_traced_window_matches_static_and_xla():
    """A TRACED window (Gemma-2's per-layer schedule rides a lax.scan) takes
    the dynamic-band operand path — it must match both the static-int band
    and the xla mask, fwd and grads, including the 2**30 'full attention
    this layer' encoding of window 0."""
    from distributed_training_guide_tpu.ops.attention import (
        multihead_attention)

    q, k, v = make_qkv(1, 64, 4, 2, 32, seed=4)

    @jax.jit
    def traced(q, k, v, w):
        return flash_attention(q, k, v, causal=True, window=w,
                               block_q=32, block_k=32, interpret=True)

    w = jnp.asarray(24, jnp.int32)
    static = flash_attention(q, k, v, causal=True, window=24,
                             block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(traced(q, k, v, w)),
                               np.asarray(static), rtol=1e-6, atol=1e-6)

    # grads through the dynamic band (the band's own cotangent is float0)
    def loss_traced(q, k, v):
        o = traced(q, k, v, w)
        return jnp.mean(o * o)

    def loss_xla(q, k, v):
        o = multihead_attention(q, k, v, causal=True, window=24, impl="xla")
        return jnp.mean(o * o)

    g_t = jax.grad(loss_traced, argnums=(0, 1, 2))(q, k, v)
    g_x = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_t, g_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=f"d{name}")

    # 2**30 = "full attention this layer" (_layer_window_column's encoding
    # of 0) degenerates to plain causal numerics
    full = traced(q, k, v, jnp.asarray(2 ** 30, jnp.int32))
    causal_ref = flash_attention(q, k, v, causal=True, block_q=32,
                                 block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(causal_ref),
                               rtol=1e-6, atol=1e-6)


def test_per_layer_window_scan_matches_unrolled():
    """The Gemma-2 shape of the plumbing: a window COLUMN riding lax.scan
    (one traced window per layer, softcap + scale active) must equal the
    per-layer unrolled static calls — the kernel grid sees one program, the
    band operand varies per scan step."""
    q, k, v = make_qkv(1, 64, 4, 2, 32, seed=5)
    extras = dict(scale=24.0 ** -0.5, logit_softcap=30.0)
    wins = jnp.asarray([24, 2 ** 30], jnp.int32)   # sliding, then full

    @jax.jit
    def scanned(q, k, v):
        def body(carry, w):
            o = flash_attention(q + carry, k, v, causal=True, window=w,
                                block_q=32, block_k=32, interpret=True,
                                **extras)
            return o, None
        out, _ = jax.lax.scan(body, jnp.zeros_like(q), wins)
        return out

    got = scanned(q, k, v)
    want = jnp.zeros_like(q)
    for w in (24, None):   # 2**30 == no band
        want = flash_attention(q + want, k, v, causal=True, window=w,
                               block_q=32, block_k=32, interpret=True,
                               **extras)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# tiles of every kind in one grid: interior (no mask), edge (masked), dead
# (skipped) under every band the callers pack
# ---------------------------------------------------------------------------

TILE_S, TILE_BQ, TILE_BK = 1024, 128, 256      # an 8 x 4 grid of tiles
# (window, q_off, k_off): causal; a static window whose lower edge crosses
# the middle of a tile (query 640 sees keys from 193 on); the ring's chunk
# pairs: the same chunk, and a past chunk under a window
BANDS = {"causal": (None, 0, 0), "window": (448, 0, 0),
         "window-512": (512, 0, 0),      # Laguna's window layers
         "ring-diagonal": (None, 1024, 1024), "ring-past": (1400, 1024, 0)}


def brute_force_tiles(window, q_off, k_off, s=TILE_S, bq=TILE_BQ, bk=TILE_BK):
    """{(iq, ik): "interior" | "edge" | "dead"} from the element mask."""
    diff = (q_off + np.arange(s))[:, None] - (k_off + np.arange(s))[None, :]
    mask = (diff >= 0) & ((diff < window) if window is not None else True)
    kinds = {}
    for iq in range(s // bq):
        for ik in range(s // bk):
            tile = mask[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
            kinds[iq, ik] = ("interior" if tile.all() else
                             "edge" if tile.any() else "dead")
    return kinds


@pytest.mark.parametrize("band", sorted(BANDS))
def test_tile_kinds_match_the_element_mask(band):
    """``_band_tile``'s two scalar predicates against the mask itself, over
    every tile of the grids the next test runs; each grid holds all three
    kinds (the ring's diagonal pair as plain causal does)."""
    window, q_off, k_off = BANDS[band]
    want = brute_force_tiles(window, q_off, k_off)
    assert set(want.values()) == {"interior", "edge", "dead"}
    for (iq, ik), kind in want.items():
        live, interior = fa._band_tile(True, window, iq, ik, TILE_BQ, TILE_BK,
                                       q_off, k_off)
        got = "dead" if not live else "interior" if interior else "edge"
        assert got == kind, (iq, ik, got, kind)
    assert fa._band_tile(False, None, 0, 3, TILE_BQ, TILE_BK) == (True, True)
    if not (q_off or k_off):
        counts = fa.tile_counts(True, window, TILE_S, TILE_S, TILE_BQ, TILE_BK)
        assert counts == tuple(sum(k == kind for k in want.values())
                               for kind in ("interior", "edge", "dead"))


@pytest.mark.parametrize("by_rows", [True, False], ids=["rows", "columns"])
@pytest.mark.parametrize("band", ["causal", "window", "window-512"])
def test_live_tiles_are_the_grid_a_static_band_walks(band, by_rows):
    """The prefetched tile lists hold exactly the live tiles, outer index
    first and each walk in order; an outer index with no live tile (kv
    columns past the last query of a shorter q) keeps one dead entry, so its
    output block is still written."""
    window = BANDS[band][0]
    kinds = brute_force_tiles(window, 0, 0)
    nq, nk = TILE_S // TILE_BQ, TILE_S // TILE_BK
    outer, inner = fa._live_tiles(True, window, nq, nk, TILE_BQ, TILE_BK,
                                  by_rows)
    walked = list(zip(outer, inner) if by_rows else zip(inner, outer))
    assert sorted(walked) == sorted(t for t, k in kinds.items() if k != "dead")
    assert list(zip(outer, inner)) == sorted(zip(outer, inner))
    # half the q rows: the kv columns past them have no live tile
    outer, inner = fa._live_tiles(True, window, nq // 2, nk, TILE_BQ, TILE_BK,
                                  by_rows)
    assert set(outer) == set(range(nq // 2 if by_rows else nk))
    dead = [(o, i) for o, i in zip(outer, inner) if not fa._band_tile(
        True, window, *((o, i) if by_rows else (i, o)), TILE_BQ, TILE_BK)[0]]
    assert dead == ([] if by_rows else [(2, 0), (3, 0)])


def test_the_line_a_flash_call_leaves_counts_its_tiles():
    """``note_attention``'s line for a flash call: the tiles ``_pick_block``
    chose (1024-wide for bf16 at head_dim 128 from four tiles a side on, 512
    otherwise or where the caller's ceiling says so) and the walk's tile
    counts under a static band."""
    from distributed_training_guide_tpu.ops import dispatch
    from distributed_training_guide_tpu.ops.attention import (
        multihead_attention)

    def line(seq, dtype, **kw):
        q = jax.ShapeDtypeStruct((2, seq, 16, 128), dtype)
        k = jax.ShapeDtypeStruct((2, seq, 8, 128), dtype)
        return fa.describe_walk(q, k, True, kw.pop("window", None), **kw)

    assert line(8192, jnp.bfloat16).startswith(
        "tiles 1024x1024, a walk: 28 interior / 8 edge / 28 dead")
    assert line(8192, jnp.bfloat16, block_q=512, block_k=512).startswith(
        "tiles 512x512, a walk: 120 interior / 16 edge / 120 dead")
    assert line(2048, jnp.bfloat16).startswith(
        "tiles 512x512, a walk: 6 interior / 4 edge / 6 dead")
    assert line(8192, jnp.float32).startswith("tiles 512x512")
    assert line(4096, jnp.bfloat16, window=1024).startswith(
        "tiles 512x512, a walk: 7 interior / 14 edge / 43 dead")
    assert line(8192, jnp.bfloat16, window=4096).startswith("tiles 1024x1024")
    assert "traced window" in line(4096, jnp.bfloat16,
                                   window=jnp.asarray(1024))
    # which operand layout the kernels took: by head_dim, and head-major
    # where the caller asks for it (the sharded wrapper's maps do)
    for seq, kw in [(2048, {}), (4096, dict(window=jnp.asarray(1024)))]:
        assert line(seq, jnp.bfloat16, **kw).endswith("; seq-major operands")
        assert line(seq, jnp.bfloat16, seq_major=False, **kw).endswith(
            "; head-major operands")
    q, k, v = make_qkv(1, 64, 4, 2, 32)
    with dispatch.record_attention() as record:
        multihead_attention(q, k, v, impl="flash")
    assert record["flash"] == ("forced; tiles 64x64, a walk: 0 interior / 1 "
                               "edge / 0 dead; head-major (head_dim 32) "
                               "operands")


EVERY_BAND = ["causal", "window", "traced-window", "ring-diagonal",
              "ring-past"]
# (band, hq, hkv, head_dim): every band at head_dim 32 (head-major operands);
# seq-major operands at the dense cells' 16 / 8 heads of 128 under a static
# and a traced band, and as the ring packs them; 64-wide heads (64 / 8 heads
# run the small grids above: a 1024-token walk of them is minutes interpreted)
TILE_CASES = ([(band, hq, hkv, 32) for hq, hkv in [(4, 2), (8, 1)]
               for band in EVERY_BAND]
              + [(band, 16, 8, 128)
                 for band in ["causal", "window-512", "traced-window"]]
              + [(band, 4, 2, 128) for band in ["ring-diagonal", "ring-past"]]
              + [(band, 4, 2, 64) for band in ["window-512", "ring-past"]])


@pytest.mark.parametrize("band,hq,hkv,d", TILE_CASES)
def test_tiles_of_every_kind_fwd_and_grads_match_xla(band, hq, hkv, d):
    """Forward and all three gradients against ``_xla_attention`` (float32)
    on a grid that holds interior, edge and dead tiles at once, on
    ``[B, S, H, D]`` operands as the model holds them. The static and traced
    bands go through the public entry; the ring's offsets through
    ``_flash_fwd`` / ``flash_bwd_with_stats`` with a packed band, as
    ``ops/ring_attention.py`` calls them on its chunks."""
    window, q_off, k_off = BANDS["window" if band == "traced-window" else band]
    assert fa._Operands(d).seq_major == (d == 128)
    assert not fa._Operands(d, seq_major=False).seq_major
    q, k, v = make_qkv(1, TILE_S, hq, hkv, d, seed=7)
    do = jax.random.normal(jax.random.key(8), q.shape, q.dtype)
    blocks = dict(block_q=TILE_BQ, block_k=TILE_BK, interpret=True)

    def xla_fn(q, k, v):
        return _xla_attention(q, k, v, True, q_off + jnp.arange(TILE_S)[None],
                              k_off + jnp.arange(TILE_S)[None], window)

    want, vjp = jax.vjp(xla_fn, q, k, v)
    want_grads = vjp(do)
    if band.startswith("ring"):
        packed = fa._pack_band(window, q_off, k_off)
        got, lse = fa._flash_fwd(q, k, v, True, None, band=packed, **blocks)
        delta = jnp.einsum("bshd,bshd->bhs", do, got)
        grads = fa.flash_bwd_with_stats(q, k, v, do, lse, delta,
                                        causal=True, band=packed, **blocks)
    else:
        if band == "traced-window":
            fn = jax.jit(lambda q, k, v, w: flash_attention(
                q, k, v, causal=True, window=w, **blocks))
            got, vjp = jax.vjp(lambda *a: fn(*a, jnp.asarray(window)), q, k, v)
        else:
            got, vjp = jax.vjp(lambda *a: flash_attention(
                *a, causal=True, window=window, **blocks), q, k, v)
        grads = vjp(do)
    assert_close(got, want, 1e-5)
    for name, a, b in zip("qkv", grads, want_grads):
        assert_close(a, b, 1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("window", [None, 40], ids=["causal", "window"])
@pytest.mark.parametrize("d", [128, 64], ids=["seq-major", "head-major"])
def test_ring_hops_on_model_layout_chunks_match_dense(eight_devices, d,
                                                      window):
    """The ring hands each hop's ``[B, S_c, H, D]`` chunks to the kernels as
    it holds them (no relayout of its own since PR 46) and merges their
    partials in that layout: forward and the three gradients against the
    dense float32 reference, as they matched it before, GQA, under both
    operand layouts, with and without a window across chunk boundaries."""
    from distributed_training_guide_tpu.ops.ring_attention import (
        make_ring_attention)
    from distributed_training_guide_tpu.parallel import make_mesh

    ring = make_ring_attention(make_mesh(cp=2, devices=eight_devices[:2]),
                               window=window)
    q, k, v = make_qkv(2, 64, 4, 2, d, seed=11)
    do = jax.random.normal(jax.random.key(12), q.shape, q.dtype)
    want, vjp = jax.vjp(lambda *a: _xla_attention(*a, True, None, None,
                                                  window), q, k, v)
    got, ring_vjp = jax.vjp(ring, q, k, v)
    assert_close(got, want, 1e-5)
    for name, a, b in zip("qkv", ring_vjp(do), vjp(do)):
        assert_close(a, b, 1e-4, err_msg=f"d{name}")
