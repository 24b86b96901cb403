#!/usr/bin/env python3
"""Each Pallas kernel of the main paths against its XLA reference, ON THE CHIP.

``chip_smoke.py`` runs this as its last one-chip phase. The serve phase's
greedy tokens are a coarse check: how much an argmax depends on attention is
a property of the (random) weights — on a tiny model a kernel that returns
ZEROS still reproduces most of the reference's tokens — so the kernels
themselves are held to their references here, on random inputs at the
smoke's own shapes, through the functions the entry points call:

- ``serve/kv_pages.paged_attend(impl="flash")`` vs ``impl="xla"`` (the gather
  reference) at qwen3-0.6b's heads (16/8 of 128), fp32 pool, page 16, T = 1
  (decode) and T = 64 (a prefill chunk), slots of several lengths, and the
  benchmark's serve cell (16 slots of 32/32 heads, bf16, 256 table columns,
  1344 pages) at T = 1 with lengths from 0 to 3000 in one call;
- ``ops/attention.multihead_attention(impl="flash")`` vs ``impl="xla"``,
  forward and backward, bf16, at the smoke's training shape (seq 2048) and
  at the benchmark's three train cells' sequence lengths, GQA ratios and
  tiles (8192 at 4/2 heads, 4096 at 8/8; batch and heads cut to what the
  reference's ``[B, H, S, S]`` scores leave room for), with its own control:
  the element mask left off the edge tiles, which the bound must refuse;
- ``ops/grouped_matmul.grouped_matmul(impl="pallas", group_offset=...)``
  reading layer 4's 32 expert matrices (4096 x 2048, bf16) out of a whole
  ``[6 * 32, K, N]`` leaf, as the MoE families' paged step hands them over,
  vs the einsum on those matrices alone.

Every paged case runs on STACKED pools of three layers with different
contents, the kernel at layer 2 against the gather path on that layer ALONE
(a one-layer stack, where no layer can be taken for another).

A case passes when ``max|kernel - reference| <= RTOL * max(1, max|reference|)``.
Then the CONTROL: the same paged comparison with the kernel sabotaged five
ways (returns zeros / reads the value pool as keys / walks the block table
rolled by one page / masks one position too many / the layer's page base
left out, so that it reads layer 0); the bound must REFUSE every one, or it
proves nothing.

``--all`` adds what is off the smoke's path but in ``ops/``: bf16 and int8
pools, page 32, T = 5 (speculative verify), the cell's shape at T = 5 and at
its chunk of 512 over a 3000-token history, banded and soft-capped flash,
``gmm``/``tgmm`` at hidden 2048 x expert width 768, the int8 matmul at
1024 x 3072 (128-wide blocks), and the ``--mla`` cases. A builder runs that
by hand. ``--mla`` runs the latent-attention cell's kernels alone
(``mistral-small-4-ep4-l6.serve.decode32-ctx8k``): the absorbed latent attend
(``paged_latent_attend``: 32 slots of 32 heads over one latent row of 256 +
64, page 128, 96 table columns, 3073 pages, bf16, T = 1, lengths 0..12,287 in
one call) against the gathered rows, with three sabotaged kernels the bound
must refuse, and ``gmm`` at hidden 4096 x expert width 2048 and back with 32
groups of 0-4 rows in a 128-row buffer (the decode step's held pairs), the
way back once more in place, at layer 5 of the stacked leaf; then the three
other MoE serve cells' decode shapes (``GMM_CELL_SHAPES``: chat64, MiMo,
Solar), into an expert plainly and out of it in place. Every ``gmm decode``
line carries the ``(bm, bn, nw)`` the call chose (``gmm_blocks``).

``--lfm2`` runs the hybrid cell's attend alone
(``lfm2-24b-a2b-l9.serve.chat64``): 64-wide heads through ``paged_attend``
over pool rows that hold two kv heads each (``models/lfm2._packed_attend``:
32 query / 8 kv heads, page 128, 10 table columns, 641 pages, 2 attention
layers, bf16), the decode step (64 slots, lengths 1..1279) and a prefill
chunk (T = 1,024, its query tokens in blocks of 128), each against the gather
path over the same rows, with one sabotage the bound must refuse (the two
heads of every pool row swapped).

``--mimo`` runs the two-class cell's attend alone
(``mimo-v2.5-ep16-l7.serve.mixed64-ctx32k``): keys of 192 in two 128-wide
pool rows beside values of 128 through ``paged_attend``
(``models/mimo_v2._paged_attend``: 64 query heads over 4 kv heads in a full
layer, over 8 with a sink logit a head and a window of 128 in a window layer,
page 128, 288 table columns a class, bf16), the decode step (contexts 5 to
36,863 in one call) and a prefill chunk (T = 2,048 after 0 and after 4,096
tokens, its query tokens in blocks), each against the gather path over the
same rows, with one sabotage the bound must refuse (the sink left out).

``--kda`` runs the state-class cell's two KDA computations alone
(``solar-open2-ep8-l4.serve.gen192``; ``ops/kda.py``): the recurrent step's
Pallas kernel at the cell's size (192 slots of 64 heads of 128 x 128, the
pool of 3 layers x 193 blocks updated in place at layer 1, slots of which a
few carry the trash block) against the gather / ``jnp`` / scatter path, the
blocks no slot holds left as they were; the chunk's Pallas kernel over 2,048
tokens from a non-zero state with 2,000 of them real (64 heads of 128, decays
down to 0.2 a step) against the recurrence in float64 on the host (four
heads, within 2e-6 of the largest element), against the ``jnp`` form (1e-6)
and against the step applied token by token in float32 (4e-6: that scan is
itself 2.5e-6 from float64, and a case says so); bfloat16 operands would
read 1e-3; and two sabotages the bound must refuse (the step's and the
chunk's decay left out).

``--ssm`` runs the state-space cell's two scans alone
(``jamba2-3b.serve.chat256``; ``ops/ssm.py``): the recurrent step's Pallas
kernel at the cell's size (256 slots of a 16 x 5,120 state, the pool of 26
layers x 257 blocks updated in place at layer 3, a few slots on the trash
block and a few at position 0) against the gather / ``jnp`` / scatter path,
the blocks no slot holds left as they were; the chunk's Pallas kernel over
1,024 tokens from a non-zero state with 1,000 of them real (``Delta A`` down
to -1.6 a step) against the ``jnp`` scan over tokens (1e-5 of the largest
element; read 0.0 on the chip: the same float32 operations in the same
order) and against the recurrence in float64 on the host (64 channels, 1e-4:
on the chip both float32 forms end 0.95e-5 of the largest ``y`` and 4.5e-5 of
the largest state element from it after 1,000 steps, the chip's float32
``exp`` and a thousand roundings a channel; no product runs on the MXU, so
nothing is rounded to bfloat16, which would read 1e-2); one sabotage each
the bound must refuse (the decay left out); and
each kernel's time at that size, a line each (``ssm_timing``).

``--retention`` runs the power-retention cell's two kernels alone
(``brumby-14b-l8.serve.doc16``; ``ops/retention.py``): one sequence of 40
query heads on 8 kv heads of 128 in bfloat16 through a FRESH chunk of 1,024
tokens, a carried chunk with 1,000 of 1,024 real and eight decode steps, on a
block of a ``[2, 17, 8, 36, 256, 128]`` float32 pool at layer 1, against the
attention form in float64 on the host (kv head 0's five query heads: no state
and no feature map in it) and against the ``jnp`` forms, outputs and state;
the decode step at the cell's 16 slots (two on the trash block, two at
position 0) against the gather / ``jnp`` / scatter path, the blocks no slot
holds left as they were; two sabotages the bound must refuse (the gate left
out of the chunk and of the step); and each kernel's time at that size, a
line (``retention_timing``).

One process; fails (no last line, exit 1) off the chip. Prints the entry
points' start-up device line, one JSON line per case, and last
``{"kernel_parity_ok": true, "cases": N, "controls_refused": 5}``.
"""
import importlib
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from distributed_training_guide_tpu.utils.compile_cache import \
    enable_compile_cache  # noqa: E402

CACHE = enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_training_guide_tpu.ops.attention import \
    multihead_attention  # noqa: E402
from distributed_training_guide_tpu.serve import kv_pages  # noqa: E402
from distributed_training_guide_tpu.utils.logging import \
    print_device_line  # noqa: E402

EXPECT_PLATFORM = "tpu"
# bf16 MXU passes on fp32 operands, one or two bf16 ulps on bf16 outputs.
# Read on the chip: 5e-3..7e-3 on fp32/int8-pool outputs of magnitude 2..3,
# 0.016 on bf16 ones, gradients within 0.021 of their magnitude; the
# sabotaged kernels of the control are off by 0.48..1.5 of it
RTOL = 0.05
HQ, HKV, D = 16, 8, 128          # qwen3-0.6b attention
N_SLOTS, PAGES_PER_SLOT, POOL_PAGES = 4, 8, 64
SEQ, BATCH = 2048, 2
N_LAYERS, LAYER = 3, 2           # the stacked pools, and the layer attended

RNG = np.random.default_rng(0)
FAILED: list = []
N_CASES = 0


def normal(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def worst(got, want) -> tuple[float, float]:
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    if not np.isfinite(got).all():
        return float("inf"), float(np.max(np.abs(want)))
    return float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))


def case(name: str, pairs: dict, rtol: float = RTOL, **shape) -> bool:
    """Record one comparison; ``pairs`` maps a label to (kernel, reference)."""
    global N_CASES
    N_CASES += 1
    errs = {k: worst(a, b) for k, (a, b) in pairs.items()}
    ok = all(e <= rtol * max(1.0, m) for e, m in errs.values())
    print(json.dumps({"kernel": name, **shape, "ok": ok, "rtol": rtol,
                      "max_abs_err_and_ref_max": errs}), flush=True)
    if not ok:
        FAILED.append(name)
    return ok


# ---- the serve attend --------------------------------------------------------

def paged_inputs(pool: str, page: int, t: int):
    dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
    q = normal((N_SLOTS, t, HQ, D), dtype)
    k_new, v_new = (normal((N_SLOTS, t, HKV, D), dtype) for _ in range(2))
    k_pool, v_pool = (normal((N_LAYERS, POOL_PAGES, page, HKV, D), dtype)
                      for _ in range(2))
    if pool == "int8":
        k_pool, v_pool = (kv_pages.quantize_kv(x) for x in (k_pool, v_pool))
    tables = jnp.asarray(RNG.permutation(np.arange(1, POOL_PAGES))
                         [:N_SLOTS * PAGES_PER_SLOT]
                         .reshape(N_SLOTS, PAGES_PER_SLOT), jnp.int32)
    room = PAGES_PER_SLOT * page - t
    # a short slot (where one masked position is a large share), one just
    # past a page, one mid-table, one that fills its table
    lengths = jnp.asarray([3, page + 1, room // 2, room - 1], jnp.int32)
    return q, k_new, v_new, k_pool, v_pool, tables, lengths


def layered(impl: str, args):
    """``paged_attend``'s arguments for one side of a case: the kernel gets
    the stacked pools and ``LAYER``; the gather reference that layer alone,
    as layer 0 of a one-layer stack."""
    q, k_new, v_new, k_pool, v_pool, *rest = args
    if impl == "flash":
        return (q, k_new, v_new, k_pool, v_pool, LAYER, *rest)
    alone = [jax.tree.map(lambda x: x[LAYER][None], pool)
             for pool in (k_pool, v_pool)]
    return (q, k_new, v_new, *alone, 0, *rest)


def attend(impl: str, args):
    fn = jax.jit(lambda *a: kv_pages.paged_attend(*a, impl=impl)[0])
    return fn(*layered(impl, args))


def paged_case(pool: str, page: int, t: int) -> None:
    args = paged_inputs(pool, page, t)
    case("paged_attend", {"out": (attend("flash", args), attend("xla", args))},
         pool=pool, page=page, T=t)


# the benchmark's serve cell (olmo2-7b-l12.serve.decode16): 16 slots of 32/32
# heads, page 16, 256 table columns, 1344 pages, bf16; lengths from an empty
# slot over both sides of a page and of a block of the walk to a long context
CELL = dict(heads=32, page=16, columns=256, pages=1344)
CELL_LENGTHS = [0, 1, 15, 16, 17, 63, 64, 65, 300, 530, 580, 612, 630, 970,
                2047, 3000]


def cell_case(t: int) -> None:
    heads, page = CELL["heads"], CELL["page"]
    lengths = CELL_LENGTHS if t <= 8 else CELL_LENGTHS[-1:]
    n = len(lengths)
    q = normal((n, t, heads, D), jnp.bfloat16)
    k_new, v_new = (normal((n, t, heads, D), jnp.bfloat16) for _ in range(2))
    k_pool, v_pool = (normal((N_LAYERS, CELL["pages"], page, heads, D),
                             jnp.bfloat16) for _ in range(2))
    tables = np.zeros((n, CELL["columns"]), np.int32)
    free = iter(RNG.permutation(np.arange(1, CELL["pages"])))
    for i, length in enumerate(lengths):
        need = -(-(length + t) // page)
        tables[i, :need] = [next(free) for _ in range(need)]
    args = (q, k_new, v_new, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))
    case("paged_attend", {"out": (attend("flash", args), attend("xla", args))},
         pool="bf16", page=page, T=t, heads=f"{heads}/{heads}", slots=n,
         lengths=f"{min(lengths)}..{max(lengths)}")


def sabotaged(mode: str):
    real = kv_pages.paged_flash_attend

    def wrapped(q, k_pages, v_pages, layer, tables, lengths, **kw):
        if mode == "swap_k_v":
            k_pages, v_pages = v_pages, k_pages
        elif mode == "wrong_pages":
            tables = jnp.roll(tables, 1, axis=1)
        elif mode == "drop_newest":
            lengths = jnp.maximum(lengths - 1, 0)
        elif mode == "no_layer_base":
            layer = 0
        out = real(q, k_pages, v_pages, layer, tables, lengths, **kw)
        return jnp.zeros_like(out) if mode == "zeros" else out

    return wrapped


def controls() -> int:
    """The bound must refuse every sabotaged kernel; returns how many it
    did. (Not a ``case``: a refusal here is the pass.)"""
    args = paged_inputs("fp32", 16, 1)
    want = attend("xla", args)
    refused = 0
    real = kv_pages.paged_flash_attend
    for mode in ("zeros", "swap_k_v", "wrong_pages", "drop_newest",
                 "no_layer_base"):
        kv_pages.paged_flash_attend = sabotaged(mode)
        try:
            err, ref = worst(attend("flash", args), want)
        finally:
            kv_pages.paged_flash_attend = real
        caught = err > RTOL * max(1.0, ref)
        refused += caught
        print(json.dumps({"control": mode, "max_abs_err": err, "ref_max": ref,
                          "rtol": RTOL, "refused": caught}), flush=True)
    return refused


# ---- the training attention --------------------------------------------------

# (batch, seq, q heads, kv heads) at head_dim 128, bf16: the smoke's, then
# the three train cells' sequence lengths and GQA ratios (qwen3-0.6b at 2048
# and 8192, olmo2-7b at 4096), which also picks their tiles; batch and heads
# cut to what the XLA reference's [B, H, S, S] fp32 scores leave room for
FLASH_SHAPES = ((BATCH, SEQ, HQ, HKV), (1, 8192, 4, 2), (1, 4096, 8, 8))


def flash_run(impl: str, shape, **kw):
    batch, seq, hq, hkv = shape
    rng = np.random.default_rng(seq)
    q, k, v = (jnp.asarray(rng.standard_normal((batch, seq, h, D)),
                           jnp.bfloat16) for h in (hq, hkv, hkv))

    def loss(q, k, v):
        out = multihead_attention(q, k, v, impl=impl, **kw)
        return (out.astype(jnp.float32) ** 2).sum(), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return dict(zip(("out", "dq", "dk", "dv"), (out, *grads)))


def flash_case(shape=FLASH_SHAPES[0], **kw) -> None:
    got, want = flash_run("flash", shape, **kw), flash_run("xla", shape, **kw)
    case("flash_attention fwd+bwd", {n: (got[n], want[n]) for n in got},
         batch=shape[0], seq=shape[1], heads=f"{shape[2]}/{shape[3]}",
         head_dim=D, **kw)


def flash_control() -> int:
    """The bound must refuse the flash kernels with the element mask left
    off their edge tiles (dead tiles still skipped: only the diagonal tiles
    differ); 1 if it did."""
    from distributed_training_guide_tpu.ops import flash_attention as flash

    shape = FLASH_SHAPES[1]
    want = flash_run("xla", shape)
    real = flash._band_mask
    flash._band_mask = lambda *a, **kw: None
    try:
        got = flash_run("flash", shape)
    finally:
        flash._band_mask = real
    errs = {n: worst(got[n], want[n]) for n in got}
    caught = all(e > RTOL * max(1.0, m) for e, m in errs.values())
    print(json.dumps({"control": "flash_edge_tiles_unmasked", "rtol": RTOL,
                      "max_abs_err_and_ref_max": errs, "refused": caught}),
          flush=True)
    return int(caught)


# ---- off the smoke's path (--all) -------------------------------------------

def gmm_case(dtype) -> None:
    gm = importlib.import_module(
        "distributed_training_guide_tpu.ops.grouped_matmul")
    rows, k, n, groups = 4096, 2048, 768, 128
    sizes = jnp.asarray(RNG.multinomial(rows - 100, np.ones(groups) / groups),
                        jnp.int32)
    lhs = normal((rows, k), dtype)
    rhs = normal((groups, k, n), dtype) * jnp.asarray(0.05, dtype)

    def run(impl):
        def loss(lhs, rhs):
            out = gm.grouped_matmul(lhs, rhs, sizes, impl=impl)
            return (out.astype(jnp.float32) ** 2).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(lhs, rhs)
        return out, grads

    (out_p, g_p), (out_e, g_e) = run("pallas"), run("einsum")
    case("gmm/tgmm", {"out": (out_p, out_e), "dlhs": (g_p[0], g_e[0]),
                      "drhs": (g_p[1], g_e[1])},
         dtype=jnp.dtype(dtype).name, hidden=k, expert_width=n, experts=groups)


# the latent cell (mistral-small-4-ep4-l6.serve.decode32-ctx8k): an empty
# slot, both sides of a page and of a block of the walk (8 pages), the
# cell's contexts, a slot that fills its table
LATENT = dict(heads=32, latent=256, rope=64, rope_width=128, page=128,
              columns=96, pages=3073)
LATENT_LENGTHS = [0, 1, 127, 128, 129, 1023, 1024, 1025, 2047, 4096, 8191,
                  8192, 8200, 8640, 9000, 9100, 9216, 9217, 10000, 11000,
                  12000, 12287] + [8200 + 37 * i for i in range(10)]


def latent_inputs():
    c = LATENT
    n = len(LATENT_LENGTHS)
    q = normal((n, 1, c["heads"], c["latent"] + c["rope"]), jnp.bfloat16)
    k_new = normal((n, 1, 1, c["rope_width"]), jnp.bfloat16)
    v_new = normal((n, 1, 1, c["latent"]), jnp.bfloat16)
    k_pool = normal((N_LAYERS, c["pages"], c["page"], 1, c["rope_width"]),
                    jnp.bfloat16)
    v_pool = normal((N_LAYERS, c["pages"], c["page"], 1, c["latent"]),
                    jnp.bfloat16)
    tables = jnp.asarray(RNG.permutation(np.arange(1, c["pages"]))
                         [:n * c["columns"]].reshape(n, c["columns"]),
                         jnp.int32)
    return (q, k_new, v_new, k_pool, v_pool, tables,
            jnp.asarray(LATENT_LENGTHS, jnp.int32))


def latent_attend(impl: str, args):
    fn = jax.jit(lambda *a: kv_pages.paged_attend(
        *a, impl=impl, scale=0.2 / LATENT["latent"] ** 0.5,
        latent_rope=LATENT["rope"])[0])
    return fn(*layered(impl, args))


def latent_cases() -> int:
    """The cell's decode attend against the gathered rows, then its control:
    the kernel reading lengths one short, one page of the table rolled, and
    layer 0's pages; returns how many of the three the bound refused."""
    args = latent_inputs()
    want = latent_attend("xla", args)
    case("paged_latent_attend", {"out": (latent_attend("flash", args), want)},
         pool="bf16", page=LATENT["page"], T=1, heads=LATENT["heads"],
         slots=len(LATENT_LENGTHS),
         lengths=f"{min(LATENT_LENGTHS)}..{max(LATENT_LENGTHS)}")
    real = kv_pages.paged_latent_attend
    refused = 0
    for mode in ("drop_newest", "wrong_pages", "no_layer_base"):
        def wrapped(q, k_pages, v_pages, layer, tables, lengths, **kw):
            if mode == "wrong_pages":
                tables = jnp.roll(tables, 1, axis=1)
            elif mode == "drop_newest":
                lengths = jnp.maximum(lengths - 1, 0)
            else:
                layer = 0
            return real(q, k_pages, v_pages, layer, tables, lengths, **kw)
        kv_pages.paged_latent_attend = wrapped
        try:
            err, ref = worst(latent_attend("flash", args), want)
        finally:
            kv_pages.paged_latent_attend = real
        caught = err > RTOL * max(1.0, ref)
        refused += caught
        print(json.dumps({"control": f"latent {mode}", "max_abs_err": err,
                          "ref_max": ref, "rtol": RTOL, "refused": caught}),
              flush=True)
    return refused


def gmm_decode_case(k: int, n: int, layer=None, rows: int = 128,
                    groups: int = 32, most: int = 4) -> None:
    """The decode step's expert products: ``groups`` held experts, 0 to
    ``most`` pairs each, in the ``rows``-row buffer (Mistral's 32 experts
    and the 128 rows of 32 tokens x top-4 unless said; rows past the held
    pairs come back zero), at the ``(bm, bn, nw)`` the call chose, which the
    case prints. With ``layer`` the kernel reads them where the serve
    path's layer scan leaves them: in the whole ``[6 * groups, K, N]`` leaf,
    from ``group_offset = layer * groups`` on (the other layers hold ones,
    fifty times the weights' size, so a kernel that read another layer is far
    outside the bound), against the einsum on that layer's matrices ALONE."""
    gm = importlib.import_module(
        "distributed_training_guide_tpu.ops.grouped_matmul")
    layers = 6
    assert groups * most <= rows
    sizes = jnp.asarray(RNG.integers(0, most + 1, groups), jnp.int32)
    lhs = normal((rows, k), jnp.bfloat16)
    rhs = normal((groups, k, n), jnp.bfloat16) * jnp.asarray(0.02, jnp.bfloat16)
    bm, bn = gm.gmm_blocks(rows, groups, k, n, lhs.dtype, rhs.dtype)
    said = dict(hidden=k, expert_width=n, experts=groups, rows=rows,
                held_pairs=int(sizes.sum()),
                bm_bn_nw=[bm, bn, gm.work_items(rows, groups, bm)])
    out_e = jax.jit(lambda a, b: gm.grouped_matmul(
        a, b, sizes, impl="einsum"))(lhs, rhs)
    if layer is None:
        out_p = jax.jit(lambda a, b: gm.grouped_matmul(
            a, b, sizes, impl="pallas"))(lhs, rhs)
        case("gmm decode", {"out": (out_p, out_e)}, **said)
        return
    stack = jax.jit(lambda b: jax.lax.dynamic_update_slice_in_dim(
        jnp.ones((layers * groups, k, n), b.dtype), b, layer * groups, 0))(rhs)
    out_p = jax.jit(lambda a, b, at: gm.grouped_matmul(
        a, b, sizes, group_offset=at, impl="pallas"))(
            lhs, stack, jnp.int32(layer * groups))
    case("gmm decode in place", {"out": (out_p, out_e)}, layer=layer,
         leaf=list(stack.shape), **said)


# the three other serve cells' decode steps, into an expert and out of it:
# chat64 (64 of 64 experts, 0-4 rows each), MiMo (16 held, 512 rows walked),
# Solar (40 held; 512 columns do not divide its 1,280); ``groups * most``
# is at most ``rows``, whatever the draw
GMM_CELL_SHAPES = [dict(k=2048, n=1536, rows=256, groups=64, most=4),
                   dict(k=4096, n=2048, rows=512, groups=16, most=24),
                   dict(k=4096, n=1280, rows=512, groups=40, most=12)]


def mla_cases() -> int:
    refused = latent_cases()
    gmm_decode_case(4096, 2048)
    gmm_decode_case(2048, 4096)
    gmm_decode_case(2048, 4096, layer=5)
    for shape in GMM_CELL_SHAPES:
        gmm_decode_case(**shape)
        gmm_decode_case(**{**shape, "k": shape["n"], "n": shape["k"]},
                        layer=2)
    return refused


# the hybrid cell (lfm2-24b-a2b-l9.serve.chat64): heads of 64 packed two to a row
HYBRID = dict(hq=32, hkv=8, d=64, page=128, columns=10, pages=641, layers=2)


def hybrid_attend(impl: str, t: int, lengths, sabotage: bool = False):
    """One attention layer's paged call as the family makes it; ``sabotage``
    swaps the two kv heads of every pool row, new rows and cached ones."""
    import dataclasses

    from distributed_training_guide_tpu.models import lfm2

    h = HYBRID
    cfg = dataclasses.replace(lfm2.PRESETS["lfm2-24b-a2b"],
                              dtype=jnp.bfloat16)
    n = len(lengths)
    rng = np.random.default_rng(7)
    draw = lambda shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q = draw((n, t, h["hq"], h["d"]))
    k_new, v_new = (draw((n, t, h["hkv"], h["d"])) for _ in range(2))
    k_pool, v_pool = (draw((h["layers"], h["pages"], h["page"],
                            h["hkv"] // 2, 2 * h["d"])) for _ in range(2))
    if sabotage:
        swap = lambda x, axis: jnp.flip(x.reshape(
            *x.shape[:axis], -1, 2, h["d"]), axis=-2).reshape(x.shape)
        k_new, v_new = swap(k_new, 2), swap(v_new, 2)
        k_pool, v_pool = swap(k_pool, 3), swap(v_pool, 3)
    tables = np.zeros((n, h["columns"]), np.int32)
    free = iter(rng.permutation(np.arange(1, h["pages"])))
    for i, length in enumerate(lengths):
        need = -(-(length + t) // h["page"])
        tables[i, :need] = [next(free) for _ in range(need)]
    lens = jnp.asarray(lengths, jnp.int32)

    def call(q, k_new, v_new, k_pool, v_pool, tables):
        hook = lfm2._packed_attend(
            cfg, kv_pages.make_attend(tables, lens, impl=impl),
            (k_pool, v_pool), 1)
        return hook(q, k_new, v_new, window=None, scale=None, softcap=None)[0]

    return jax.jit(call)(q, k_new, v_new, k_pool, v_pool, jnp.asarray(tables))


def lfm2_cases(chunks: bool = True) -> int:
    """The decode step (the smoke's path too), and with ``chunks`` two
    prefill chunks and the control; returns how many controls were refused."""
    decode = [1, 2, 127, 128, 129, 255, 256, 640, 1023, 1024, 1025, 1279] * 5 \
        + [300, 700, 900, 1100]
    shapes = ((1, decode), (1024, [0]), (1024, [256]))
    for t, lengths in shapes if chunks else shapes[:1]:
        case("paged_attend packed 64-wide heads",
             {"out": (hybrid_attend("flash", t, lengths),
                      hybrid_attend("xla", t, lengths))},
             pool="bf16", page=128, T=t, heads="32/8 of 64, two a row",
             slots=len(lengths), lengths=f"{min(lengths)}..{max(lengths)}")
    if not chunks:
        return 0
    want = hybrid_attend("xla", 1, decode)
    err, ref = worst(hybrid_attend("flash", 1, decode, sabotage=True), want)
    caught = err > RTOL * max(1.0, ref)
    print(json.dumps({"control": "a_rows_two_heads_swapped", "max_abs_err": err,
                      "ref_max": ref, "rtol": RTOL, "refused": caught}),
          flush=True)
    return int(caught)


# the two-class cell (mimo-v2.5-ep16-l7.serve.mixed64-ctx32k): keys of 192 in
# two 128-wide pool rows, values of 128, 64 query heads over 4 kv heads (full
# layers) or 8 with a sink logit a head and a window of 128 (window layers)
MIMO = dict(hq=64, dk=192, dv=128, page=128, layers=2, layer=1,
            full=dict(hkv=4, columns=288, pages=2000),
            window=dict(hkv=8, columns=288, pages=147))


def mimo_attend(impl: str, kind: str, t: int, lengths, sink: bool = True,
                columns=None):
    """One attention layer's paged call as ``models/mimo_v2.py`` makes it,
    over the pools of ONE page class: a full layer walks every live page, a
    window layer the pages its window reaches (its table names only those,
    zeros elsewhere, as the scheduler builds it). ``sink`` False leaves the
    window layers' sink logits out (the control)."""
    import dataclasses

    from distributed_training_guide_tpu.models import mimo_v2

    m, c = MIMO, dict(MIMO[kind], **({"columns": columns} if columns else {}))
    cfg = dataclasses.replace(mimo_v2.PRESETS["mimo-v2.5"], dtype=jnp.bfloat16)
    n = len(lengths)
    rng = np.random.default_rng(11)
    draw = lambda shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q = draw((n, t, m["hq"], m["dk"]))
    k_new = draw((n, t, c["hkv"], m["dk"]))
    v_new = draw((n, t, c["hkv"], m["dv"]))
    k_pool = draw((m["layers"] * cfg.key_parts, c["pages"], m["page"],
                   c["hkv"], cfg.row_width))
    v_pool = draw((m["layers"], c["pages"], m["page"], c["hkv"],
                   cfg.row_width))
    sinks = jnp.asarray(rng.standard_normal(m["hq"]), jnp.float32)
    window = cfg.sliding_window if kind == "window" else None
    tables = np.zeros((n, 2 * c["columns"]), np.int32)
    first = c["columns"] if kind == "window" else 0     # the class's half
    free = iter(rng.permutation(np.arange(1, c["pages"])))
    for i, length in enumerate(lengths):
        lo = 0 if window is None else max(length - (window - 1), 0) // m["page"]
        for col in range(lo, -(-(length + t) // m["page"])):
            tables[i, first + col] = next(free)
    lens = jnp.asarray(lengths, jnp.int32)

    def call(q, k_new, v_new, k_pool, v_pool, tables, sinks):
        hook = mimo_v2._paged_attend(
            cfg, kv_pages.make_attend(tables, lens, impl=impl),
            (k_pool, v_pool), m["layer"], kind)
        return hook(q, k_new, v_new, window=window, scale=m["dk"] ** -0.5,
                    sink=sinks if kind == "window" and sink else None)[0]

    return jax.jit(call)(q, k_new, v_new, k_pool, v_pool,
                         jnp.asarray(tables), sinks)


def mimo_cases() -> int:
    """Both kinds at the decode step (64 slots, contexts from a few tokens to
    the 36,864 cap, either side of page and block edges) and at a prefill
    chunk's query tile (2,048 tokens after 0 and after 4,096), against the
    gather path over the same pools; then the control: the sink left out of
    the kernel's call, which the bound must refuse."""
    # 24 slots: the gather path's copy of a slot's 288 table columns is 75 MB
    # a pool row in the window class (8 heads), three rows a token
    spread = [5, 127, 128, 129, 255, 256, 257, 511, 512, 513, 1023, 2047,
              4095, 4096, 8191, 12000, 16383, 20000, 32767, 36863, 640, 3000,
              9000, 30000]
    for kind, lengths in (("full", spread), ("window", spread)):
        # a chunk against 48 table columns: the gather path's scores of 2,048
        # query tokens over all 288 would be 19 GB
        shapes = ((1, lengths, None), (2048, [0], 48), (2048, [4096], 48))
        for t, lens, columns in shapes:
            case(f"paged_attend {kind} layer, keys 192 in two rows",
                 {"out": (mimo_attend("flash", kind, t, lens, columns=columns),
                          mimo_attend("xla", kind, t, lens, columns=columns))},
                 pool="bf16", page=128, T=t, kind=kind,
                 heads=f"64/{MIMO[kind]['hkv']} of 192/128",
                 slots=len(lens), lengths=f"{min(lens)}..{max(lens)}")
    want = mimo_attend("xla", "window", 1, spread)
    err, ref = worst(mimo_attend("flash", "window", 1, spread, sink=False),
                     want)
    caught = err > RTOL * max(1.0, ref)
    print(json.dumps({"control": "sink_left_out", "max_abs_err": err,
                      "ref_max": ref, "rtol": RTOL, "refused": caught}),
          flush=True)
    return int(caught)


def kda_inputs(shape, low_rate=True):
    """Rows of the recurrence at ``shape = (..., H)`` with 128-wide heads:
    unit k, q of length 1 / sqrt(128), decays 0.2-0.999 a step, beta in
    (0, 2)."""
    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    d = 128
    q = unit(normal((*shape, d), jnp.float32)) * d ** -0.5
    k = unit(normal((*shape, d), jnp.float32))
    v = normal((*shape, d), jnp.float32)
    g = -jnp.asarray(RNG.uniform(0.001, 1.6, (*shape, d)), jnp.float32)
    beta = 2 * jax.nn.sigmoid(normal(shape, jnp.float32))
    return q, k, v, g, beta


def kda_cases() -> int:
    from distributed_training_guide_tpu.ops import kda

    slots, heads, layers, blocks = 192, 64, 3, 193
    pool = normal((layers, blocks, heads, 128, 128), jnp.float32)
    ids = RNG.permutation(np.arange(1, blocks)).astype(np.int32)
    ids[[5, 77, 190]] = kda.TRASH_BLOCK
    ids = jnp.asarray(ids)
    rows = kda_inputs((slots, heads))

    def step(impl, rows=rows):
        return jax.jit(lambda pool: kda.kda_step(pool, ids, 1, *rows,
                                                 impl=impl))(pool)

    (o, new), (o_ref, new_ref) = step("pallas"), step("xla")
    # the slots that hold a sequence: the trash block is read and written by
    # every idle slot in turn, and what they read out means nothing
    held = np.flatnonzero(np.asarray(ids) != kda.TRASH_BLOCK)
    live = np.asarray(ids)[held]
    idle = np.setdiff1d(np.arange(1, blocks), live)
    case("kda_step", {"o": (o[held], o_ref[held]),
                      "state": (new[1, live], new_ref[1, live])},
         slots=slots, heads=heads, blocks=blocks)
    untouched = bool(jnp.array_equal(new[1, idle], pool[1, idle])
                     and jnp.array_equal(new[0], pool[0])
                     and jnp.array_equal(new[2], pool[2]))
    case("kda_step_leaves_other_blocks", {"same": (
        jnp.asarray(float(untouched)), jnp.asarray(1.0))})
    # the sabotage: no decay
    q, k, v, g, beta = rows
    o_bad, _ = step("pallas", (q, k, v, jnp.zeros_like(g), beta))
    refused = int(not case("kda_step_control_no_decay",
                           {"o": (o_bad[held], o_ref[held])}))
    if refused:     # the control is meant to fail its bound
        FAILED.remove("kda_step_control_no_decay")

    # the chunk kernel at the cell's size, float32 against float32: a
    # product on bfloat16 operands would read 1e-3 here
    t, real = 2048, 2000
    s0 = normal((1, heads, 128, 128), jnp.float32)
    crow = kda_inputs((1, t, heads))

    def chunk(impl, rows=crow):
        return jax.jit(lambda s0: kda.kda_chunk(
            s0, *rows, jnp.asarray([real]), impl=impl))(s0)

    (o, s_t), (o_jnp, s_jnp) = chunk("pallas"), chunk("xla")

    def token(s, row):
        o, s = kda.delta_step(s, *row)
        return s, o

    want_s, want_o = jax.jit(lambda s0, rows: jax.lax.scan(
        token, s0, jax.tree.map(lambda x: jnp.moveaxis(x[:, :real], 1, 0),
                                rows)))(s0, crow)
    want_o = jnp.moveaxis(want_o, 0, 1)
    # the arbiter: the same recurrence in float64 on the host, four heads.
    # On the chip the kernel ends 1.4e-6 of the largest element from it
    # (PR 45) and the stepped float32 scan, which rounds 2,000 times a
    # channel where a blocked form rounds 32, 2.5e-6: so the kernel is held
    # to 2e-6 of float64, 1e-6 of the ``jnp`` form (0.7e-6 read) and 4e-6 of
    # the stepped scan (2.2e-6 read); bfloat16 operands would read 1e-3
    some = np.asarray([0, 21, 42, heads - 1])
    q64, k64, v64, g64, b64 = (np.asarray(x, np.float64)[0, :real][:, some]
                               for x in crow)
    s64 = np.asarray(s0, np.float64)[0, some]
    o64 = np.zeros((real, len(some), 128))
    for i in range(real):
        s64 = s64 * np.exp(g64[i])[..., None]
        u = b64[i][:, None] * (v64[i] - np.einsum("hk,hkv->hv", k64[i], s64))
        s64 = s64 + k64[i][..., None] * u[:, None, :]
        o64[i] = np.einsum("hk,hkv->hv", q64[i], s64)
    o64, s64 = jnp.asarray(o64, jnp.float32), jnp.asarray(s64, jnp.float32)
    case("kda_chunk", {"o": (o[0, :real][:, some], o64),
                       "state": (s_t[0, some], s64)},
         rtol=2e-6, tokens=t, real=real, heads=heads, against="float64 scan")
    case("kda_chunk_jnp_form", {"o": (o[:, :real], o_jnp[:, :real]),
                                "state": (s_t, s_jnp)},
         rtol=1e-6, tokens=t, real=real, heads=heads)
    case("kda_chunk_stepped", {"o": (o[:, :real], want_o),
                               "state": (s_t, want_s)},
         rtol=4e-6, tokens=t, real=real, heads=heads)
    case("kda_stepped_scan_itself", {"o": (want_o[0][:, some], o64),
                                     "state": (want_s[0, some], s64)},
         rtol=4e-6, against="float64 scan")
    q, k, v, g, beta = crow
    o_bad, s_bad = chunk("pallas", (q, k, v, jnp.zeros_like(g), beta))
    if not case("kda_chunk_control_no_decay",
                {"o": (o_bad[:, :real], want_o), "state": (s_bad, want_s)}):
        FAILED.remove("kda_chunk_control_no_decay")
        refused += 1
    return refused


def ssm_inputs(shape, channels=5120, n=16):
    """Rows of the scan at ``shape = (...)``: x of order one, a step
    log-uniform in [0.001, 0.1], B and C of order one; A = -(1..N), D = 1."""
    x = normal((*shape, channels), jnp.float32)
    delta = jnp.asarray(np.exp(RNG.uniform(
        np.log(0.001), np.log(0.1), (*shape, channels))), jnp.float32)
    b, c = normal((*shape, n), jnp.float32), normal((*shape, n), jnp.float32)
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, channels))
    return x, delta, b, c, a, jnp.ones((channels,), jnp.float32)


def timed(fn, x, carry=False, reps=20) -> float:
    """Milliseconds a call of ``fn(x)``, after one that compiles; ``carry``:
    each call takes the last one's result (a program that donates ``x``)."""
    out = jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(out if carry else x)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def ssm_cases() -> int:
    from distributed_training_guide_tpu.ops import ssm

    slots, layers, blocks, n, ch = 256, 26, 257, 16, 5120
    pool = normal((layers, blocks, n, ch), jnp.float32)
    ids = RNG.permutation(np.arange(1, blocks)).astype(np.int32)
    ids[[5, 77, 190]] = ssm.TRASH_BLOCK
    fresh = np.zeros(slots, bool)
    fresh[[9, 100]] = True
    ids, fresh = jnp.asarray(ids), jnp.asarray(fresh)
    rows = ssm_inputs((slots,))

    def step(impl, rows=rows):
        return jax.jit(lambda pool: ssm.ssm_step(pool, ids, 3, *rows, fresh,
                                                 impl=impl))(pool)

    (y, new), (y_ref, new_ref) = step("pallas"), step("xla")
    held = np.flatnonzero(np.asarray(ids) != ssm.TRASH_BLOCK)
    live = np.asarray(ids)[held]
    idle = np.setdiff1d(np.arange(1, blocks), live)
    case("ssm_step", {"y": (y[held], y_ref[held]),
                      "state": (new[3, live], new_ref[3, live])},
         rtol=1e-5, slots=slots, blocks=blocks, layers=layers)
    others = np.setdiff1d(np.arange(layers), [3])
    untouched = bool(jnp.array_equal(new[3, idle], pool[3, idle])
                     and jnp.array_equal(new[others], pool[others]))
    case("ssm_step_leaves_other_blocks", {"same": (
        jnp.asarray(float(untouched)), jnp.asarray(1.0))})
    x, delta, b, c, a, d = rows
    y_bad, _ = step("pallas", (x, delta, b, c, jnp.zeros_like(a), d))
    refused = int(not case("ssm_step_control_no_decay",
                           {"y": (y_bad[held], y_ref[held])}, rtol=1e-5))
    if refused:     # the control is meant to fail its bound
        FAILED.remove("ssm_step_control_no_decay")
    del new, new_ref
    step_ms = timed(jax.jit(lambda pool: ssm.ssm_step(
        pool, ids, 3, *rows, fresh, impl="pallas")[1], donate_argnums=0),
        pool, carry=True)
    del pool

    t, real = 1024, 1000
    h0 = normal((1, n, ch), jnp.float32)
    crow = ssm_inputs((1, t))
    nv = jnp.asarray([real])

    def chunk(impl, rows=crow):
        return jax.jit(lambda h0: ssm.ssm_chunk(h0, *rows, nv, impl=impl))(h0)

    (y, h_t), (y_jnp, h_jnp) = chunk("pallas"), chunk("xla")
    some = np.arange(0, ch, ch // 64)
    x64, d64, b64, c64 = (np.asarray(v, np.float64)[0, :real] for v in crow[:4])
    a64 = np.asarray(crow[4], np.float64)[:, some]
    h64 = np.asarray(h0, np.float64)[0][:, some]
    y64 = np.zeros((real, len(some)))
    for i in range(real):
        h64 = (np.exp(d64[i, some][None, :] * a64) * h64
               + (d64[i, some] * x64[i, some])[None, :] * b64[i][:, None])
        y64[i] = c64[i] @ h64 + x64[i, some]
    case("ssm_chunk", {"y": (y[0, :real][:, some], y64),
                       "state": (h_t[0][:, some], h64)},
         rtol=1e-4, tokens=t, real=real, against="float64 scan")
    case("ssm_chunk_jnp_form", {"y": (y[:, :real], y_jnp[:, :real]),
                                "state": (h_t, h_jnp)},
         rtol=1e-5, tokens=t, real=real)
    x, delta, b, c, a, d = crow
    y_bad, h_bad = chunk("pallas", (x, delta, b, c, jnp.zeros_like(a), d))
    if not case("ssm_chunk_control_no_decay",
                {"y": (y_bad[:, :real], y_jnp[:, :real]),
                 "state": (h_bad, h_jnp)}, rtol=1e-5):
        FAILED.remove("ssm_chunk_control_no_decay")
        refused += 1
    chunk_ms = timed(jax.jit(lambda h0: ssm.ssm_chunk(
        h0, *crow, nv, impl="pallas")), h0)
    print(json.dumps({"ssm_timing": {
        "ssm_step_ms_a_layer_256_slots": step_ms,
        "ssm_chunk_ms_a_layer_1024_tokens": chunk_ms,
        "channels_a_block": ssm.CHANNELS, "tokens_a_block": ssm.TOKENS}}),
        flush=True)
    return refused


def retention_cases(hq=40, hkv=8, d=128, t=1024, real=1000, steps=8) -> int:
    from distributed_training_guide_tpu.ops import retention as ret

    layers, blocks, layer, block = 2, 17, 1, 3
    g, total = hq // hkv, 2 * t + steps
    shapes = ret.state_shapes(hkv, d)
    pools = tuple(normal((layers, blocks, *shape), jnp.float32)
                  for shape in shapes)
    q, k, v = (normal((1, total, h, d), jnp.bfloat16)
               for h in (hq, hkv, hkv))
    gate = jnp.asarray(RNG.standard_normal((1, total, hkv)) * 1.4 + 6.906768,
                       jnp.float32)
    log_gamma = jax.nn.log_sigmoid(gate)
    ids = jnp.asarray([block])
    # the rows the sequence really has: chunk 1 whole, 1,000 of chunk 2, then
    # the steps' (the 24 padded rows of chunk 2 belong to no sequence)
    rows = np.r_[0:t, t:t + real, 2 * t:total]

    def run(impl, lg):
        """Outputs at the real rows, and the pools after."""
        def chunk(pools, lo, fresh, n):
            return jax.jit(lambda s, z: ret.retention_chunk(
                s, z, ids, layer, q[:, lo:lo + t], k[:, lo:lo + t],
                v[:, lo:lo + t], lg[:, lo:lo + t], jnp.asarray([fresh]),
                jnp.asarray([n]), impl=impl))(*pools)

        o1, *state = chunk(pools, 0, True, t)
        o2, *state = chunk(state, t, False, real)
        outs = [o1[0], o2[0, :real]]
        step = jax.jit(lambda s, z, i: ret.retention_step(
            s, z, ids, layer, q[:, i], k[:, i], v[:, i], lg[:, i],
            jnp.asarray([False]), impl=impl))
        for i in range(2 * t, total):
            o, *state = step(*state, i)
            outs.append(o)
        return jnp.concatenate(outs), state

    (o, state), (o_jnp, state_jnp) = run("pallas", log_gamma), run(
        "xla", log_gamma)
    # the attention form in float64, kv head 0's query heads
    q64, k64, v64, c64 = (np.asarray(x, np.float64)[0][rows] for x in (
        q[:, :, :g], k[:, :, 0], v[:, :, 0], log_gamma[:, :, 0]))
    run_c = np.cumsum(c64)
    o64 = np.zeros((len(rows), g, d))
    for j in range(g):
        score = q64[:, j] @ k64.T
        w = np.tril(score * score * np.exp(np.minimum(
            run_c[:, None] - run_c[None, :], 0.0)))
        o64[:, j] = (w @ v64) / w.sum(1, keepdims=True)
    case("retention_chunk_and_step", {"o": (o[:, :g], o64)}, rtol=1e-4,
         tokens=total, real=len(rows), against="float64 attention form")
    case("retention_jnp_forms", {
        "o": (o, o_jnp), "S": (state[0][layer, block],
                               state_jnp[0][layer, block]),
        "Z": (state[1][layer, block], state_jnp[1][layer, block])},
        rtol=1e-5)
    o_bad, _ = run("pallas", jnp.zeros_like(log_gamma))
    refused = 0
    for name, at in (("retention_chunk_control_no_gate", slice(t, t + real)),
                     ("retention_step_control_no_gate", slice(-steps, None))):
        if not case(name, {"o": (o_bad[at], o_jnp[at])}, rtol=1e-5):
            FAILED.remove(name)
            refused += 1
    # the decode step at the cell's 16 slots, every slot on the sequence's
    # state (its block copied into all 16), rows of its own
    slots = 16
    pools = tuple(jnp.broadcast_to(leaf[:, block][:, None], leaf.shape)
                  for leaf in state)
    slot_ids = np.arange(1, blocks).astype(np.int32)
    slot_ids[[2, 11]] = ret.TRASH_BLOCK
    fresh = np.zeros(slots, bool)
    fresh[[5, 9]] = True
    slot_ids, fresh = jnp.asarray(slot_ids), jnp.asarray(fresh)
    sq, sk, sv = (normal((slots, h, d), jnp.bfloat16) for h in (hq, hkv, hkv))
    slg = log_gamma[0, :slots]

    def step16(impl):
        return jax.jit(lambda s, z: ret.retention_step(
            s, z, slot_ids, layer, sq, sk, sv, slg, fresh, impl=impl))(*pools)

    (o, s_new, z_new), (o_ref, s_ref, z_ref) = step16("pallas"), step16("xla")
    held = np.flatnonzero(np.asarray(slot_ids) != ret.TRASH_BLOCK)
    live = np.asarray(slot_ids)[held]
    # (a slot at position 0 reads out ONE token's state: num and den are
    # both (q . k)^2 times something, and where q . k is near 0 the quotient
    # is rounding's; its S and Z are compared, its o is not)
    old = np.flatnonzero((np.asarray(slot_ids) != ret.TRASH_BLOCK)
                         & ~np.asarray(fresh))
    case("retention_step_16_slots", {
        "o": (o[old], o_ref[old]),
        "S": (s_new[layer, live], s_ref[layer, live]),
        "Z": (z_new[layer, live], z_ref[layer, live])}, rtol=1e-5,
        slots=slots, blocks=blocks)
    idle = np.setdiff1d(np.arange(1, blocks), live)
    untouched = bool(jnp.array_equal(s_new[layer, idle], pools[0][layer, idle])
                     and jnp.array_equal(s_new[0], pools[0][0]))
    case("retention_step_leaves_other_blocks", {"same": (
        jnp.asarray(float(untouched)), jnp.asarray(1.0))})
    del s_new, z_new, s_ref, z_ref
    step_ms = timed(jax.jit(lambda sz: ret.retention_step(
        *sz, slot_ids, layer, sq, sk, sv, slg, fresh, impl="pallas")[1:],
        donate_argnums=0), pools, carry=True)
    chunk_ms = {}
    for name, is_fresh in (("carried", False), ("fresh", True)):
        chunk_ms[name] = timed(jax.jit(lambda sz, f=is_fresh:
            ret.retention_chunk(*sz, ids, layer, q[:, :t], k[:, :t], v[:, :t],
                                log_gamma[:, :t], jnp.asarray([f]),
                                impl="pallas")[1:], donate_argnums=0),
            tuple(state), carry=True, reps=10)
        state = [jnp.array(x) for x in state_jnp]
    print(json.dumps({"retention_timing": {
        "retention_step_ms_a_layer_16_slots": step_ms,
        "retention_chunk_ms_a_layer_1024_tokens": chunk_ms,
        "pairs_a_step_tile": ret.PAIR_TILE, "tokens_a_block": ret.TOKENS}}),
        flush=True)
    return refused


def int8_matmul_case() -> None:
    qm = importlib.import_module(
        "distributed_training_guide_tpu.ops.quantized_matmul")
    k, n, block = 1024, 3072, 128
    w = RNG.standard_normal((k, n)).astype(np.float32).reshape(k, n // block,
                                                               block)
    scale = np.abs(w).max(-1) / 127.0
    payload = np.clip(np.round(w / scale[..., None]), -127, 127)
    weight = SimpleNamespace(q=jnp.asarray(payload.reshape(k, n), jnp.int8),
                             scale=jnp.asarray(scale, jnp.float32))
    x = normal((8, k), jnp.float32)
    got, want = (jax.jit(lambda x, impl=impl: qm.quantized_matmul(
        x, weight, impl=impl))(x) for impl in ("pallas", "xla"))
    case("quantized_matmul", {"out": (got, want)}, k=k, n=n, block=block)


def main(argv) -> int:
    everything, mla_only = argv == ["--all"], argv == ["--mla"]
    lfm2_only, mimo_only = argv == ["--lfm2"], argv == ["--mimo"]
    kda_only, ssm_only = argv == ["--kda"], argv == ["--ssm"]
    retention_only = argv == ["--retention"]
    if argv and not (everything or mla_only or lfm2_only or mimo_only
                     or kda_only or ssm_only or retention_only):
        raise SystemExit("usage: kernel_parity.py [--all|--mla|--lfm2|--mimo|"
                         "--kda|--ssm|--retention]")
    print_device_line("attend", ("flash", "forced"), CACHE.directory)
    if jax.devices()[0].platform != EXPECT_PLATFORM:
        print(f"kernel_parity FAILED: runs on "
              f"{jax.devices()[0].platform!r}, not {EXPECT_PLATFORM!r}",
              file=sys.stderr)
        return 1
    if retention_only:
        refused = retention_cases()
        CACHE.print_line()
        if FAILED or refused != 2:
            print(f"kernel_parity FAILED: cases over the bound: {FAILED}; "
                  f"gate left out refused: {refused} of 2", file=sys.stderr)
            return 1
        print(json.dumps({"kernel_parity_ok": True, "cases": N_CASES,
                          "controls_refused": refused}), flush=True)
        return 0
    if ssm_only:
        refused = ssm_cases()
        CACHE.print_line()
        if FAILED or refused != 2:
            print(f"kernel_parity FAILED: cases over the bound: {FAILED}; "
                  f"decay left out refused: {refused} of 2", file=sys.stderr)
            return 1
        print(json.dumps({"kernel_parity_ok": True, "cases": N_CASES,
                          "controls_refused": refused}), flush=True)
        return 0
    if kda_only:
        refused = kda_cases()
        CACHE.print_line()
        if FAILED or refused != 2:
            print(f"kernel_parity FAILED: cases over the bound: {FAILED}; "
                  f"decay left out refused: {refused} of 2", file=sys.stderr)
            return 1
        print(json.dumps({"kernel_parity_ok": True, "cases": N_CASES,
                          "controls_refused": refused}), flush=True)
        return 0
    if mimo_only:
        refused = mimo_cases()
        CACHE.print_line()
        if FAILED or refused != 1:
            print(f"kernel_parity FAILED: cases over the bound: {FAILED}; "
                  f"sink left out refused: {refused} of 1", file=sys.stderr)
            return 1
        print(json.dumps({"kernel_parity_ok": True, "cases": N_CASES,
                          "controls_refused": refused}), flush=True)
        return 0
    if lfm2_only:
        refused = lfm2_cases()
        CACHE.print_line()
        if FAILED or refused != 1:
            print(f"kernel_parity FAILED: cases over the bound: {FAILED}; "
                  f"swapped heads refused: {refused} of 1", file=sys.stderr)
            return 1
        print(json.dumps({"kernel_parity_ok": True, "cases": N_CASES,
                          "controls_refused": refused}), flush=True)
        return 0
    if mla_only:
        refused = mla_cases()
        CACHE.print_line()
        if FAILED or refused != 3:
            print(f"kernel_parity FAILED: cases over the bound: {FAILED}; "
                  f"sabotaged latent kernels refused: {refused} of 3",
                  file=sys.stderr)
            return 1
        print(json.dumps({"kernel_parity_ok": True, "cases": N_CASES,
                          "controls_refused": refused}), flush=True)
        return 0
    for t in (1, 64):
        paged_case("fp32", 16, t)
    cell_case(1)
    for shape in FLASH_SHAPES:
        flash_case(shape)
    flash_refused = flash_control()
    gmm_decode_case(4096, 2048, layer=4)
    lfm2_cases(chunks=everything)
    latent_refused = 3
    if everything:
        latent_refused = mla_cases()
        for t in (5, 512):
            cell_case(t)
        for pool in ("fp32", "bf16", "int8"):
            for page in (16, 32):
                for t in (1, 5, 64):
                    if (pool, page, t) not in (("fp32", 16, 1),
                                               ("fp32", 16, 64)):
                        paged_case(pool, page, t)
        flash_case(window=512)
        flash_case(logit_softcap=30.0)
        for dtype in (jnp.bfloat16, jnp.float32):
            gmm_case(dtype)
        int8_matmul_case()
    refused = controls()
    CACHE.print_line()
    if FAILED or refused != 5 or latent_refused != 3 or flash_refused != 1:
        print(f"kernel_parity FAILED: cases over the bound: {FAILED}; "
              f"sabotaged kernels refused: {refused} of 5, latent "
              f"{latent_refused} of 3, flash {flash_refused} of 1",
              file=sys.stderr)
        return 1
    print(json.dumps({"kernel_parity_ok": True, "cases": N_CASES,
                      "controls_refused": refused + flash_refused}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
