"""Compile every Pallas kernel of the main paths for a DESCRIBED TPU v5e.

The one file in the suite that describes a chip. The TPU's compiler is
installed here and compiles for a ``v5e:2x2`` that is described and not
attached, so what it refuses (a block shape Mosaic cannot tile, more VMEM
than a kernel may plan for, a shape cast it has no layout for) is found at
no chip time. Every case passes ``interpret=False`` explicitly — under
``JAX_PLATFORMS=cpu`` an ``interpret=None`` default resolves to the
interpreter, which would test nothing — and asserts that the kernel is in
the compiled program (``tpu_custom_call``).

Shapes are real ones: qwen3-0.6b's attention (seq 2048, 16/8 heads of 128),
the serve CLI's page sizes for fp32, bf16 and int8 pools at T=1 (decode),
T=5 (spec verify) and one prefill-chunk size (always STACKED pools of three
layers and a traced layer index, as the layer scan hands them over), the
benchmark's serve cell
(16 slots of 32/32 heads, 256 table columns, 1344 pages; its chunk of 512, a
GQA pool, an int8 pool, a one-head slice of a sharded pool), qwen3-30b-a3b's expert GEMMs
(hidden 2048 x expert width 768) and an int8 projection (1024 x 3072).

A pass here is a compile, never a run: nothing executes, and no result or
time comes out of it.

The topology is described inside a module-scoped fixture (never at import:
only one process may load libtpu, and every xdist worker imports every test
file), compiles happen in this process, and the persistent compile cache is
off around them (an entry compiled for a described chip cannot be read back
without one, and would warn on every later run).
"""
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_training_guide_tpu.ops.flash_attention import flash_attention
from distributed_training_guide_tpu.ops.paged_decode import (
    paged_decode_eligible, paged_flash_attend)

# ops/__init__ re-exports the grouped_matmul FUNCTION under the module's name
gmm_mod = importlib.import_module(
    "distributed_training_guide_tpu.ops.grouped_matmul")
qmm_mod = importlib.import_module(
    "distributed_training_guide_tpu.ops.quantized_matmul")

SEQ, HQ, HKV, D = 2048, 16, 8, 128          # qwen3-0.6b attention
N_LAYERS = 3                                # the stacked pools of the attend
LAYER = ((), jnp.int32)                     # its traced layer index


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_compile(one_chip):
    """``compile(fn, *(shape, dtype), donate=())`` -> compiled HLO text for
    the chip (``donate``: argument numbers the program donates, as the serve
    engine donates its pools), with the persistent cache off while this
    module's tests run."""
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *specs, donate=()):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn, donate_argnums=donate).lower(
            *args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def kernel_calls(text: str) -> list:
    """Names of the ``tpu_custom_call`` instructions of a compiled program."""
    return re.findall(r'%([\w.]+) = [^\n]*custom-call\([^\n]*'
                      r'custom_call_target="tpu_custom_call"', text)


def named(call: str, name: str) -> bool:
    return re.search(rf"(^|_){name}(_|\.|$)", call) is not None


# an instruction with an array result: its name, dtype, dims and opcode
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]+)\][^ ]* "
                          r"([\w\-]+)\(")


def pool_sized_ops(text: str, *pool_shapes, names: bool = False) -> list:
    """Opcodes (with ``names``: the instructions' names too, ``opcode
    name``) of the compiled program's instructions whose array result holds
    as many elements as one of the stacked ``[L, ...]`` pools or as one layer
    of it."""
    counts = {n for shape in pool_shapes
              for n in (math.prod(shape), math.prod(shape[1:]))}
    sized = []
    for line in text.splitlines():
        found = _INSTRUCTION.match(line)
        if found and math.prod(
                int(x) for x in found.group(3).split(",")) in counts:
            sized.append(f"{found.group(4)} {found.group(1)}" if names
                         else found.group(4))
    return sized


def assert_pools_carried_in_place(text: str, *pool_shapes) -> None:
    """A whole serve program moves nothing of the pools' (or of one layer's)
    size: what has that size are the parameters, what renames them, and the
    two in-place ``scatter``s of the new rows with the fusions that hold
    them. No ``copy``, no ``dynamic-slice`` or ``dynamic-update-slice`` (bare
    or as a fusion's name), no layout ``custom-call``: each was a read and a
    write of a whole pool in every layer of every step."""
    sized = pool_sized_ops(text, *pool_shapes, names=True)
    assert sum(x.startswith("scatter ") for x in sized) == 2, sized
    moved = [x for x in sized
             if x.split()[0] not in ("parameter", "bitcast", "scatter",
                                     "get-tuple-element", "fusion")
             or "slice" in x or "copy" in x]
    assert not moved, moved


def assert_experts_read_in_place(text: str, *leaf_shapes) -> None:
    """The expert leaves' twin of ``assert_pools_carried_in_place``: what has
    a stacked ``[L, E, K, N]`` expert leaf's element count, or one layer's
    share of it, are the parameters and what renames them (``bitcast``, the
    loop's tuple). No ``dynamic-slice``, ``copy`` or fusion of that size:
    with the leaves among the layer scan's sliced columns each layer copied
    all E matrices of each leaf out for ``gmm`` (three fusions named
    ``dynamic-slice_bitcast_fusion`` in the compiled decode step)."""
    sized = pool_sized_ops(text, *leaf_shapes, names=True)
    assert sum(x.startswith("parameter ") for x in sized) >= len(leaf_shapes)
    moved = [x for x in sized if x.split()[0] not in (
        "parameter", "bitcast", "get-tuple-element")]
    assert not moved, moved


# ---- training attention ----------------------------------------------------

# the four train cells' attention, (batch, seq, q heads, kv heads), bf16
# at head_dim 128: qwen3-0.6b at 2048 and 8192, olmo2-7b's one-chip share of
# the FSDP cell, Laguna's window and full layers
CELL_SHAPES = {"seq2048": (8, 2048, 16, 8), "seq8192": (2, 8192, 16, 8),
               "fsdp4.seq4096": (2, 4096, 32, 32),
               "laguna.window": (2, 8192, 64, 8),
               "laguna.full": (2, 8192, 48, 8)}
FLASH_CASES = ([("seq2048", extras) for extras in (
    {}, {"window": 512}, {"logit_softcap": 30.0})]
    + [(cell, {}) for cell in ("seq8192", "fsdp4.seq4096")]
    + [("laguna.window", {"window": 512}), ("laguna.full", {})])
FLASH_IDS = ["causal", "banded", "softcap", "seq8192", "fsdp4.seq4096",
             "laguna.window", "laguna.full"]
FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def operand_sized_moves(text: str, *shapes, dtypes=("bf16", "f32"),
                        rank=None) -> list:
    """``opcode name dtype[dims]`` of the compiled program's relayouts of an
    array as large as one of ``shapes``: a ``copy``, a ``transpose``, a
    ``reshape`` the compiler could not make a bitcast, or a fusion named
    after one (``rank``: of results with that many dimensions alone)."""
    counts = {math.prod(shape) for shape in shapes}
    moves = []
    for line in text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name, dtype, dims, op = found.groups()
        dims = [int(x) for x in dims.split(",")]
        if (math.prod(dims) in counts and dtype in dtypes
                and rank in (None, len(dims))
                and (op in ("copy", "transpose", "reshape") or op == "fusion"
                     and re.search("copy|transpose", name))):
            moves.append(f"{op} {name} {dtype}{dims}")
    return moves


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("cell,extras", FLASH_CASES, ids=FLASH_IDS)
def test_flash_attention_compiles(chip_compile, cell, extras, backward):
    """The flash kernels at the four train cells' shapes (and the smoke's
    banded and soft-capped ones), forward and backward, under their own
    names and no other kernel."""
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               **extras)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    b, seq, hq, hkv = CELL_SHAPES[cell]
    qs = ((b, seq, hq, D), jnp.bfloat16)
    ks = ((b, seq, hkv, D), jnp.bfloat16)
    calls = kernel_calls(chip_compile(fwd_bwd if backward else fwd, qs, ks, ks))
    names = FLASH_KERNELS if backward else FLASH_KERNELS[:1]
    assert len(calls) == len(names), calls
    for name in names:
        assert any(named(c, name) for c in calls), (name, calls)


@pytest.mark.parametrize("cell,extras", FLASH_CASES, ids=FLASH_IDS)
def test_flash_kernels_take_the_projections_arrays_as_they_lie(
        chip_compile, cell, extras):
    """Forward and backward on q, k, v as the projections leave them and o
    as the output projection takes it, ``[B, S, H*D]``, viewed ``[B, S, H,
    D]`` for the call as ``attention_sublayer`` views them: the three
    kernels under their names and NOTHING of q's, k's, v's or o's size
    moved before, between or behind them: no ``copy``, ``transpose``,
    relayouting ``reshape`` or fusion named after one (until PR 46 the
    kernels addressed ``[B, H, S, D]`` and each operand and gradient was
    transposed). The operands are 3-D on purpose: a 4-D PARAMETER's tiled
    layout on the chip tiles (H, D), so its ``[B, S, H*D]`` view is a
    relayout there; in a model the 4-D array lives inside the fusions
    between a projection and the call alone (the test below)."""
    b, seq, hq, hkv = CELL_SHAPES[cell]

    def heads(x):
        return x.reshape(b, seq, -1, D)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            o = flash_attention(heads(q), heads(k), heads(v), causal=True,
                                interpret=False, **extras)
            return o.reshape(b, seq, -1).astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    qs = ((b, seq, hq * D), jnp.bfloat16)
    ks = ((b, seq, hkv * D), jnp.bfloat16)
    text = chip_compile(fwd_bwd, qs, ks, ks)
    calls = kernel_calls(text)
    assert len(calls) == 3, calls
    for name in FLASH_KERNELS:
        assert any(named(c, name) for c in calls), (name, calls)
    assert not operand_sized_moves(text, qs[0], ks[0])


def test_attention_sublayers_relayout_no_heads_in_float32(chip_compile,
                                                          monkeypatch):
    """Two of ``models/llama.py``'s attention sublayers at the seq2048
    cell's shape (qwen3-0.6b: ``[8, 2048, 1024]`` bf16, 16 / 8 heads of 128,
    per-head QK-norm, rope), scanned with ``jax.checkpoint`` round each as
    the model scans its layers, forward and backward with the Mosaic kernels
    on. Until PR 46 each projection's float32 result was copied whole into
    the kernels' head-major layout in front of the QK-norm (134 MB for q,
    67 for k, forward and rematted) and each gradient back (four float32
    4-D copies in this program). Now the norm and rope fusions run in the
    projection's own layout and NO float32 ``copy`` or ``transpose`` of a
    ``[B, S, H, D]`` array is left; what is (PERF.md section 7): one bf16
    copy of q and of k in front of ``flash_fwd``, and dq's and dk's float32
    3-D copies into the layout the projections' backward products take."""
    from distributed_training_guide_tpu.models import get_model, llama
    from distributed_training_guide_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "resolve_interpret", lambda i: False)
    config = get_model("qwen3-0.6b").config
    b, seq, layers = CELL_SHAPES["seq2048"][0], SEQ, 2
    e, d = config.hidden_size, config.head_size
    hq, hkv = config.num_heads, config.num_kv_heads
    assert (e, hq, hkv, d, config.qk_norm) == (1024, HQ, HKV, D, True)
    shapes = {"wq": (e, hq * d), "wk": (e, hkv * d), "wv": (e, hkv * d),
              "wo": (hq * d, e), "q_norm": (d,), "k_norm": (d,), "norm": (e,)}
    names = sorted(shapes)

    def fwd_bwd(x, *leaves):
        def loss(x, params):
            positions = jnp.broadcast_to(jnp.arange(seq)[None], (b, seq))

            def layer(x, p):
                p = dict(p)
                return x + llama.attention_sublayer(
                    config, x, p, p.pop("norm"), positions, "flash"), None

            x, _ = jax.lax.scan(jax.checkpoint(layer), x, params)
            return x.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1))(x, dict(zip(names, leaves)))

    text = chip_compile(fwd_bwd, ((b, seq, e), config.dtype), *(
        ((layers, *shapes[n]), config.param_dtype) for n in names))
    calls = kernel_calls(text)
    assert [sum(named(c, n) for c in calls) for n in FLASH_KERNELS] == [
        2, 1, 1], calls            # forward, rematted forward; backward
    assert not operand_sized_moves(text, (b, seq, hq, d), (b, seq, hkv, d),
                                   dtypes=("f32",), rank=4)


# ---- the serve attend: decode, verify, chunk ------------------------------

@pytest.mark.parametrize("t", [1, 5, 64], ids=["decode", "verify", "chunk"])
@pytest.mark.parametrize("pool,page", [
    ("fp32", 16), ("fp32", 32), ("bf16", 16), ("bf16", 32),
    ("int8", 16), ("int8", 32)])
def test_paged_attend_compiles(chip_compile, pool, page, t):
    """The CLI's default ``--page-size 16`` and its int8 advice of 32, for
    every pool dtype: a page is DMA'd as its ``[page * Hkv, D]`` rows, whole
    sublane tiles of all three payloads, and the chunk takes the heads of
    one 32-bit word with a strided load."""
    assert paged_decode_eligible(D, page)
    n_slots, n_pages, table = 4, 128, 32
    q_dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
    pool_dtype = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                  "int8": jnp.int8}[pool]
    specs = [((n_slots, t, HQ, D), q_dtype),
             ((N_LAYERS, n_pages, page, HKV, D), pool_dtype),
             ((N_LAYERS, n_pages, page, HKV, D), pool_dtype), LAYER,
             ((n_slots, table), jnp.int32), ((n_slots,), jnp.int32)]
    if pool == "int8":
        specs += [((N_LAYERS, n_pages, page, HKV), jnp.float32)] * 2

        def attend(q, k, v, layer, tabs, lens, ks, vs):
            return paged_flash_attend(q, k, v, layer, tabs, lens, k_scale=ks,
                                      v_scale=vs, interpret=False)
    else:
        def attend(q, k, v, layer, tabs, lens):
            return paged_flash_attend(q, k, v, layer, tabs, lens,
                                      interpret=False)

    assert "tpu_custom_call" in chip_compile(attend, *specs)


# the serve cell's real shapes (olmo2-7b-l12.serve.decode16: 16 slots, 32/32
# heads of 128, page 16, max_len 4096 = 256 table columns, 1344 pages, bf16)
# and what else runs the kernel: (slots, T, Hq, Hkv, pool, q dtype)
CELL_PAGES, CELL_PAGE, CELL_COLUMNS = 1344, 16, 256
CELL_CASES = {
    "cell-decode": (16, 1, 32, 32, jnp.bfloat16, jnp.bfloat16),
    "cell-verify": (16, 5, 32, 32, jnp.bfloat16, jnp.bfloat16),
    "cell-chunk512": (1, 512, 32, 32, jnp.bfloat16, jnp.bfloat16),
    "cell-int8": (16, 1, 32, 32, jnp.int8, jnp.bfloat16),
    "gqa-decode": (16, 1, 32, 8, jnp.bfloat16, jnp.bfloat16),
    "gqa-chunk512": (1, 512, 32, 8, jnp.bfloat16, jnp.bfloat16),
    # llama's 32/8 heads on tp=8: serve/sharding.py hands each chip one head
    "one-head-slice": (16, 1, 4, 1, jnp.bfloat16, jnp.bfloat16),
    "one-head-chunk512": (1, 512, 4, 1, jnp.bfloat16, jnp.bfloat16),
}


def _cell_attend(chip_compile, case):
    slots, t, hq, hkv, pool_dtype, q_dtype = CELL_CASES[case]
    pool = ((N_LAYERS, CELL_PAGES, CELL_PAGE, hkv, D), pool_dtype)
    specs = [((slots, t, hq, D), q_dtype), pool, pool, LAYER,
             ((slots, CELL_COLUMNS), jnp.int32), ((slots,), jnp.int32)]
    if pool_dtype == jnp.int8:
        specs += [((N_LAYERS, CELL_PAGES, CELL_PAGE, hkv), jnp.float32)] * 2
        return pool, chip_compile(
            lambda q, k, v, layer, tabs, lens, ks, vs: paged_flash_attend(
                q, k, v, layer, tabs, lens, k_scale=ks, v_scale=vs,
                interpret=False), *specs)
    return pool, chip_compile(
        lambda *a: paged_flash_attend(*a, interpret=False), *specs)


@pytest.mark.parametrize("case", sorted(CELL_CASES))
def test_paged_attend_compiles_at_the_serve_cells_shapes(chip_compile, case):
    _, text = _cell_attend(chip_compile, case)
    calls = kernel_calls(text)
    assert len(calls) == 1 and named(calls[0], "paged_attend"), calls


@pytest.mark.parametrize("case", ["cell-decode", "cell-chunk512",
                                  "gqa-decode", "one-head-slice"])
def test_paged_attend_moves_nothing_pool_sized(chip_compile, case):
    """The kernel reads the STACKED pools where they lie, at a traced layer.
    Besides the parameters and the custom call, the compiled attend has no
    instruction whose result has the pools' element count (or one layer's)
    but the two ``bitcast``s that rename ``[L, P, page, Hkv, D]`` as
    ``[L * P, page * Hkv, D]`` (the same bytes in the same tiled layout: a
    bitcast moves nothing): no layer's pool is sliced out for the call. An
    earlier kernel took ``[P, page, Hkv * D]``, a change of tiled layout, and
    paid a ``copy`` of each pool in every layer of every decode step; the one
    after it took one layer's pool, which the layer scan had to slice out."""
    (shape, _), text = _cell_attend(chip_compile, case)
    sized = pool_sized_ops(text, shape)
    assert sorted(sized) == ["bitcast", "bitcast", "parameter", "parameter"], (
        sized)


@pytest.mark.parametrize("slots,t", [(16, 1), (1, 512)],
                         ids=["decode", "chunk512"])
def test_paged_attend_compiles_at_head_dim_256(chip_compile, slots, t):
    """Gemma-2-9b's heads (16/8 of 256, a window, a softcap): the gate takes
    head_dim 256, and a chunk there takes all heads in one product (the
    strided pick of one word's heads needs rows of one 128-lane tile)."""
    pool = ((N_LAYERS, 512, 16, 8, 256), jnp.bfloat16)
    text = chip_compile(
        lambda *a: paged_flash_attend(*a, window=4096, softcap=50.0,
                                      interpret=False),
        ((slots, t, 16, 256), jnp.bfloat16), pool, pool, LAYER,
        ((slots, 256), jnp.int32), ((slots,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_attend_gate_matches_the_compiler(chip_compile):
    """Where the gate says no, the compiler says no, and the forced path
    raises the gate's own error first: a pool row of 64 columns is half a
    lane tile of a page's ``[page * Hkv, D]`` rows (a family with 64-wide
    heads stores two a row: ``models/lfm2.py``)."""
    assert not paged_decode_eligible(64, 16)
    specs = [((4, 1, 16, 64), jnp.float32),
             ((N_LAYERS, 64, 16, 8, 64), jnp.float32),
             ((N_LAYERS, 64, 16, 8, 64), jnp.float32), LAYER,
             ((4, 8), jnp.int32), ((4,), jnp.int32)]
    with pytest.raises(ValueError, match="row width % 128"):
        chip_compile(lambda *a: paged_flash_attend(*a, interpret=False),
                     *specs)


# ---- MoE expert GEMMs and the int8 projection ------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_grouped_matmul_compiles_forward_and_backward(chip_compile, dtype):
    """``gmm`` forward plus the backward's ``gmm`` (d_lhs) and ``tgmm``
    (d_rhs) at hidden 2048 x expert width 768, 128 experts. The blocks are
    fitted to the scoped-VMEM budget (512x512 fp32 blocks at K=2048 are
    refused: 19 MiB of the 16 MiB a kernel may plan for)."""
    m, k, n, g = 8192, 2048, 768, 128

    def fwd_bwd(lhs, rhs, sizes):
        def loss(lhs, rhs):
            out = gmm_mod.grouped_matmul(lhs, rhs, sizes, impl="pallas",
                                         interpret=False)
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(0, 1))(lhs, rhs)

    text = chip_compile(fwd_bwd, ((m, k), dtype), ((g, k, n), dtype),
                        ((g,), jnp.int32))
    assert text.count("tpu_custom_call") >= 3       # gmm, gmm^T, tgmm


def test_quantized_matmul_compiles_and_refuses_narrow_blocks(chip_compile):
    """The int8 dequant matmul at 1024 x 3072: 128-wide blocks compile; the
    32-wide blocks ``serve/weights.py`` stores are not a lane tile, so the
    forced path raises (and ``auto`` says it took XLA)."""
    from types import SimpleNamespace

    k, n = 1024, 3072

    def matmul(x, q, scale):
        return qmm_mod.quantized_matmul(x, SimpleNamespace(q=q, scale=scale),
                                        impl="pallas", interpret=False)

    specs = lambda bs: (((8, k), jnp.float32), ((k, n), jnp.int8),
                        ((k, n // bs), jnp.float32))
    assert "tpu_custom_call" in chip_compile(matmul, *specs(128))
    with pytest.raises(ValueError, match="block width % 128"):
        chip_compile(matmul, *specs(32))


# ---- the kernels' own names in the compiled program -------------------------

def _flash_fwd_bwd(q, k, v):
    return jax.grad(lambda *a: flash_attention(
        *a, causal=True, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _gmm_fwd_bwd(lhs, rhs, sizes):
    return jax.grad(lambda l, r: (gmm_mod.grouped_matmul(
        l, r, sizes, impl="pallas", interpret=False).astype(
            jnp.float32) ** 2).sum(), argnums=(0, 1))(lhs, rhs)


def _qmm(x, q, scale):
    from types import SimpleNamespace

    return qmm_mod.quantized_matmul(x, SimpleNamespace(q=q, scale=scale),
                                    impl="pallas", interpret=False)


KERNEL_NAME_CASES = {
    "flash": (_flash_fwd_bwd,
              [((2, SEQ, HQ, D), jnp.bfloat16)]
              + [((2, SEQ, HKV, D), jnp.bfloat16)] * 2,
              ("flash_fwd", "flash_dq", "flash_dkv")),
    "paged": (lambda *a: paged_flash_attend(*a, interpret=False),
              [((4, 1, HQ, D), jnp.bfloat16)]
              + [((N_LAYERS, 128, 16, HKV, D), jnp.bfloat16)] * 2
              + [LAYER, ((4, 32), jnp.int32), ((4,), jnp.int32)],
              ("paged_attend",)),
    "grouped": (_gmm_fwd_bwd,
                [((1024, 256), jnp.bfloat16), ((8, 256, 256), jnp.bfloat16),
                 ((8,), jnp.int32)],
                ("gmm", "tgmm")),
    "int8": (_qmm, [((8, 1024), jnp.float32), ((1024, 3072), jnp.int8),
                    ((1024, 24), jnp.float32)], ("qmm",)),
}


@pytest.mark.parametrize("case", sorted(KERNEL_NAME_CASES))
def test_kernels_carry_their_names(chip_compile, case):
    """Each ``pallas_call``'s ``name=`` (``utils/trace.py: KERNELS``) names
    the custom call in the program the chip's compiler makes, which is the
    name a trace's ``XLA Ops`` line prints: bare (``%flash_fwd.17``) where a
    ``named_scope`` encloses the call, as in the model, and inside the
    transform's wrapper (``%transpose_jvp_flash_dq__.1``) where none does, as
    here. The call is still a ``tpu_custom_call``, which the benchmark's
    roofline readers match."""
    from distributed_training_guide_tpu.utils.trace import KERNELS

    fn, specs, names = KERNEL_NAME_CASES[case]
    calls = kernel_calls(chip_compile(fn, *specs))
    assert calls
    for name in names:
        assert name in KERNELS
        assert any(named(c, name) for c in calls), (name, calls)
    for call in calls:      # and no kernel without a name
        assert any(named(call, name) for name in names), call


# ---- the latent-attention family's cell -------------------------------------
# mistral-small-4-ep4-l6.serve.decode32-ctx8k: 32 slots of 32 heads over one
# latent row of 256 + 64 (the rope key's rows padded to 128 lanes), page 128,
# 96 table columns, 3073 pages, 6 layers, 32 of 128 experts held, bf16

LATENT_CELL = dict(slots=32, heads=32, latent=256, rope=64, rope_width=128,
                   page=128, columns=96, pages=3073)


def test_latent_attend_compiles_at_the_cells_shape(chip_compile):
    from distributed_training_guide_tpu.ops.paged_decode import (
        latent_decode_eligible, paged_latent_attend)

    c = LATENT_CELL
    assert latent_decode_eligible(c["latent"], c["rope_width"], c["page"],
                                  rows=c["heads"])
    text = chip_compile(
        lambda *a: paged_latent_attend(*a, scale=0.1, interpret=False),
        ((c["slots"], 1, c["heads"], c["latent"] + c["rope"]), jnp.bfloat16),
        ((6, c["pages"], c["page"], 1, c["rope_width"]), jnp.bfloat16),
        ((6, c["pages"], c["page"], 1, c["latent"]), jnp.bfloat16), LAYER,
        ((c["slots"], c["columns"]), jnp.int32), ((c["slots"],), jnp.int32))
    calls = kernel_calls(text)
    assert calls and all(named(x, "paged_latent_attend") for x in calls)
    # the stacked pools reach the kernel as they are stored: nothing of a
    # layer's size moves, no layer is sliced out
    assert sorted(pool_sized_ops(
        text, (6, c["pages"], c["page"], 1, c["rope_width"]),
        (6, c["pages"], c["page"], 1, c["latent"]))) == [
            "bitcast", "bitcast", "parameter", "parameter"]
    assert f"bf16[6,{c['pages']},{c['page']},1,{c['latent']}]" in text


# the four serve cells' decode steps as ``gmm`` sees them: (rows M, held
# experts G, layers of the stacked leaf, K, N), both ways through an expert
GMM_DECODE_SHAPES = {
    "mistral-in": (128, 32, 6, 4096, 2048),
    "mistral-out": (128, 32, 6, 2048, 4096),
    "chat64-in": (256, 64, 8, 2048, 1536),
    "chat64-out": (256, 64, 8, 1536, 2048),
    "mimo-in": (512, 16, 6, 4096, 2048),
    "mimo-out": (512, 16, 6, 2048, 4096),
    "solar-in": (512, 40, 4, 4096, 1280),    # bn 512 does not divide N
    "solar-out": (512, 40, 4, 1280, 4096),
}
GMM_DECODE = pytest.mark.parametrize("shape", sorted(GMM_DECODE_SHAPES))


@GMM_DECODE
def test_grouped_matmul_compiles_at_the_decode_steps_shape(chip_compile,
                                                           shape):
    """The held experts over the decode step's row buffer (Mistral: 32
    experts, the 128 rows of 32 tokens x top-4), at the blocks the call
    chooses for itself (a 64-row tile, not the buffer's rows,
    and 512 columns of the matrix a DMA; ``tests/test_grouped_matmul.py`` has
    the table): the described v5e holds their VMEM."""
    m, g, _, k, n = GMM_DECODE_SHAPES[shape]
    bm, bn = gmm_mod.gmm_blocks(m, g, k, n, jnp.bfloat16, jnp.bfloat16)
    assert bm < m and bn == 512
    text = chip_compile(
        lambda lhs, rhs, sizes: gmm_mod.grouped_matmul(
            lhs, rhs, sizes, impl="pallas", interpret=False),
        ((m, k), jnp.bfloat16), ((g, k, n), jnp.bfloat16),
        ((g,), jnp.int32))
    assert any(named(c, "gmm") for c in kernel_calls(text))


@GMM_DECODE
def test_grouped_matmul_reads_a_layer_of_the_stacked_leaf(chip_compile,
                                                          shape):
    """The same call on the whole ``[L * G, K, N]`` leaf with a traced group
    offset (``layer * G``): the kernel takes the parameter as it lies, and
    nothing of the leaf's size or of one layer's share is sliced or copied."""
    m, g, layers, k, n = GMM_DECODE_SHAPES[shape]
    text = chip_compile(
        lambda lhs, rhs, sizes, offset: gmm_mod.grouped_matmul(
            lhs, rhs, sizes, group_offset=offset, impl="pallas",
            interpret=False),
        ((m, k), jnp.bfloat16), ((layers * g, k, n), jnp.bfloat16),
        ((g,), jnp.int32), ((), jnp.int32))
    assert any(named(c, "gmm") for c in kernel_calls(text))
    assert_experts_read_in_place(text, (layers, g, k, n))


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The family's programs as the chip runs them: under a described
    topology ``jax.default_backend()`` is the CPU, so ``interpret=None`` and
    ``impl="auto"`` would pick the interpreter and the scan; the test steers
    them here, not the program."""
    from distributed_training_guide_tpu.models import moe
    from distributed_training_guide_tpu.ops import paged_decode

    monkeypatch.setattr(paged_decode, "resolve_interpret", lambda i: False)
    monkeypatch.setattr(gmm_mod, "resolve_interpret", lambda i: False)
    monkeypatch.setattr(gmm_mod, "_resolve_impl",
                        lambda impl: "pallas" if impl == "auto" else impl)
    # the dispatch's walk is traced once a shape, whatever it resolved then
    moe._walk_jit.clear_cache()
    yield
    moe._walk_jit.clear_cache()


@pytest.mark.parametrize("program", ["decode", "chunk2048"])
def test_latent_familys_serve_programs_compile_at_the_cells_size(
        chip_compile, one_chip, compiled_kernels, program):
    """The whole decode step and the whole prefill chunk at the cell's size
    (10.1 GiB of weights, the 1.69 GiB pool): the compiler fits them into the
    chip's 15.75 GiB, the decode step holds the latent kernel and three
    ``gmm`` calls a layer (one scan body), the chunk no latent kernel (it
    decompresses the gathered rows), and neither moves anything of the
    donated pools' size (the layer scan carries them) or of an expert leaf's
    (``gmm`` reads the layer's matrices where the stacked leaf lies)."""
    import dataclasses

    from distributed_training_guide_tpu.models import mla
    from distributed_training_guide_tpu.serve import kv_pages

    c = LATENT_CELL
    cfg = dataclasses.replace(
        mla.PRESETS["mistral-small-4-119b"], num_layers=6, vocab_size=32768,
        experts_held=(0, 32), dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: mla.init(cfg, jax.random.key(0)))
    pools = {leaf: ((6, c["pages"], c["page"], *shape), jnp.bfloat16)
             for leaf, shape in cfg.kv_layout().items()}
    leaves, treedef = jax.tree.flatten(params)

    def decode(kp, vp, tokens, lengths, tables, *flat):
        p = jax.tree.unflatten(treedef, flat)
        logits, cache = mla.paged_decode_step(
            cfg, p, tokens[:, None], lengths, {"k": kp, "v": vp},
            kv_pages.make_attend(tables, lengths, impl="flash"))
        return jnp.argmax(logits, -1), cache["k"], cache["v"], cache["routing"]

    def chunk(kp, vp, ids, start, table, *flat):
        p = jax.tree.unflatten(treedef, flat)
        logits, cache = mla.paged_decode_step(
            cfg, p, ids, start, {"k": kp, "v": vp},
            kv_pages.make_attend(table, start, impl="flash",
                                 n_valid=jnp.asarray([2048])),
            last_index=jnp.asarray(2047))
        return logits[0], cache["k"], cache["v"]

    weights = [(x.shape, x.dtype) for x in leaves]
    if program == "decode":
        text = chip_compile(decode, pools["k"], pools["v"],
                            ((32,), jnp.int32), ((32,), jnp.int32),
                            ((32, c["columns"]), jnp.int32), *weights,
                            donate=(0, 1))
        calls = kernel_calls(text)
        assert sum(named(x, "paged_latent_attend") for x in calls) == 1
        assert sum(named(x, "gmm") for x in calls) == 3
    else:
        text = chip_compile(chunk, pools["k"], pools["v"],
                            ((1, 2048), jnp.int32), ((1,), jnp.int32),
                            ((1, c["columns"]), jnp.int32), *weights,
                            donate=(0, 1))
        calls = kernel_calls(text)
        assert not any(named(x, "paged_latent_attend") for x in calls)
        # 8,192 pairs against a prefix of 4,096 (moe.compact_rows): the
        # compact walk's three and its full-width overflow branch's three
        assert sum(named(x, "gmm") for x in calls) == 2 * 3
    assert_pools_carried_in_place(text, *(shape for shape, _ in pools.values()))
    assert_experts_read_in_place(
        text, *(params["layers"]["moe"][leaf].shape
                for leaf in ("gate", "up", "down")))


@pytest.mark.parametrize("program", ["decode", "chunk1024"])
def test_hybrid_familys_serve_programs_compile_at_the_cells_size(
        chip_compile, compiled_kernels, program):
    """``lfm2-24b-a2b-l9.serve.chat64``'s decode step (64 slots) and prefill
    chunk (1,024 tokens), whole, at the cell's size (9.99 GiB of weights,
    k and v pools of 2 attention layers and the state pool of 7 conv layers
    over 641 pages of 128): heads of 64 go through the compiled
    ``paged_attend`` over rows of two kv heads (one call an attention layer;
    the chunk's query tokens in blocks of 128 inside one loop), ``gmm``
    three times an expert layer (the layers are walked, not scanned), and
    nothing expert-sized or pool-sized is copied: every pool-sized result is
    a parameter, a rename, an in-place ``scatter`` (two an attention layer,
    one a conv layer) or the fusion that holds one."""
    import dataclasses
    import json
    from pathlib import Path

    from distributed_training_guide_tpu.models import lfm2
    from distributed_training_guide_tpu.serve import kv_pages

    real = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                       / "configs" / "lfm2-24b-a2b-l9.json").read_text())
    cfg = dataclasses.replace(
        lfm2.PRESETS["lfm2-24b-a2b"], layer_types=tuple(real["layer_types"]),
        num_dense_layers=real["num_dense_layers"], dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: lfm2.init(cfg, jax.random.key(0)))
    leaves, treedef = jax.tree.flatten(params)
    weights = [(x.shape, x.dtype) for x in leaves]
    pools = jax.eval_shape(lambda: kv_pages.init_pages(cfg, 641, 128))
    assert pools["k"].shape == (2, 641, 128, 4, 128)
    slots, t = (64, 1) if program == "decode" else (1, 1024)

    def step(kp, vp, sp, ids, lengths, tables, *flat):
        logits, cache = lfm2.paged_decode_step(
            cfg, jax.tree.unflatten(treedef, flat), ids, lengths,
            {"k": kp, "v": vp, "state": sp},
            kv_pages.make_attend(tables, lengths, impl="flash",
                                 n_valid=jnp.full((slots,), t)),
            last_index=jnp.asarray(t - 1))
        return (jnp.argmax(logits, -1), cache["k"], cache["v"],
                cache["state"], cache["routing"])

    text = chip_compile(
        step, *((pools[n].shape, pools[n].dtype) for n in ("k", "v", "state")),
        ((slots, t), jnp.int32), ((slots,), jnp.int32),
        ((slots, 10), jnp.int32), *weights, donate=(0, 1, 2))
    calls = kernel_calls(text)
    assert sum(named(x, "gmm") for x in calls) == 3 * 8, calls
    assert sum(named(x, "paged_attend") for x in calls) == 2, calls
    sized = pool_sized_ops(text, pools["k"].shape, pools["state"].shape,
                           names=True)
    assert sum(x.startswith("scatter ") for x in sized) == 2 * 2 + 7, sized
    moved = [x for x in sized
             if x.split()[0] not in ("parameter", "bitcast", "scatter",
                                     "get-tuple-element", "fusion")
             or "slice" in x or "copy" in x]
    assert not moved, moved
    assert_experts_read_in_place(
        text, *(params["layers"]["moe"][leaf].shape
                for leaf in ("gate", "up", "down")))


MIMO_CELL = dict(slots=64, columns=288, pages=7170, window_pages=147,
                 chunk=2048)


def _mimo_cell_config():
    import dataclasses
    import json
    from pathlib import Path

    from distributed_training_guide_tpu.models import mimo_v2

    real = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                       / "configs" / "mimo-v2.5-ep16-l7.json").read_text())
    return dataclasses.replace(
        mimo_v2.PRESETS["mimo-v2.5"],
        hybrid_layer_pattern=tuple(real["hybrid_layer_pattern"]),
        moe_layer_freq=tuple(real["moe_layer_freq"]),
        vocab_size=real["vocab_size"],
        experts_held=(real["experts_held_first"], real["n_routed_experts"]),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


@pytest.mark.parametrize("program", ["decode", "chunk2048"])
def test_two_class_familys_serve_programs_compile_at_the_cells_size(
        chip_compile, compiled_kernels, program):
    """``mimo-v2.5-ep16-l7.serve.mixed64-ctx32k``'s decode step (64 slots)
    and prefill chunk (2,048 tokens), whole, at the cell's size (6.4 GiB of
    weights, the full class's pools of 2 layers over 7,170 pages and the
    window class's of 5 layers over 147): every attention layer goes through
    the compiled ``paged_attend`` at key rows of 256 (192 live) and value
    rows of 128, 16 query heads a kv head in the full layers and 8 with the
    sink column in the window layers (the chunk's query tokens in blocks,
    each inside one loop), ``gmm`` three times an expert layer, and nothing
    expert-sized or pool-sized is copied: every pool-sized result is a
    parameter, a rename, an in-place ``scatter`` (two a layer) or the fusion
    that holds one."""
    from distributed_training_guide_tpu.models import mimo_v2
    from distributed_training_guide_tpu.serve import kv_pages

    c = MIMO_CELL
    cfg = _mimo_cell_config()
    params = jax.eval_shape(lambda: mimo_v2.init(cfg, jax.random.key(0)))
    leaves, treedef = jax.tree.flatten(params)
    weights = [(x.shape, x.dtype) for x in leaves]
    pools = jax.eval_shape(lambda: kv_pages.init_pages(
        cfg, c["pages"], 128, n_window_pages=c["window_pages"]))
    assert pools["k"].shape == (2 * 2, c["pages"], 128, 4, 128)
    assert pools["v_win"].shape == (5, c["window_pages"], 128, 8, 128)
    names = ("k", "v", "k_win", "v_win")
    slots, t = (c["slots"], 1) if program == "decode" else (1, c["chunk"])

    def step(kp, vp, kw, vw, ids, lengths, tables, *flat):
        logits, cache = mimo_v2.paged_decode_step(
            cfg, jax.tree.unflatten(treedef, flat), ids, lengths,
            dict(zip(names, (kp, vp, kw, vw))),
            kv_pages.make_attend(tables, lengths, impl="flash",
                                 n_valid=jnp.full((slots,), t)),
            last_index=jnp.asarray(t - 1))
        return (jnp.argmax(logits, -1), *(cache[n] for n in names),
                cache["routing"])

    text = chip_compile(
        step, *((pools[n].shape, pools[n].dtype) for n in names),
        ((slots, t), jnp.int32), ((slots,), jnp.int32),
        ((slots, 2 * c["columns"]), jnp.int32), *weights,
        donate=(0, 1, 2, 3))
    calls = kernel_calls(text)
    # a chunk's 16,384 pairs a layer against a prefix of 2,048
    # (moe.compact_rows): the compact walk and its overflow branch
    walks = 1 if program == "decode" else 2
    assert sum(named(x, "gmm") for x in calls) == walks * 3 * 6, calls
    assert sum(named(x, "paged_attend") for x in calls) == 7, calls
    sized = pool_sized_ops(text, *(pools[n].shape for n in names), names=True)
    assert sum(x.startswith("scatter ") for x in sized) >= 3 * 7, sized
    moved = [x for x in sized
             if x.split()[0] not in ("parameter", "bitcast", "scatter",
                                     "get-tuple-element", "fusion")
             or "slice" in x or "copy" in x]
    assert not moved, moved
    assert_experts_read_in_place(
        text, *(params["layers"]["moe"][leaf].shape
                for leaf in ("gate", "up", "down")))


SOLAR_CELL = dict(slots=192, columns=28, pages=5377, chunk=2048)


@pytest.mark.parametrize("program", ["decode", "chunk2048"])
def test_state_class_familys_serve_programs_compile_at_the_cells_size(
        one_chip, chip_compile, compiled_kernels, monkeypatch, program):
    """``solar-open2-ep8-l4.serve.gen192``'s decode step (192 slots) and
    prefill chunk (2,048 tokens: its three KDA layers each through ONE
    ``kda_chunk`` kernel), whole, at the cell's size (6.16 GiB of
    weights, k and v of the one GQA layer over 5,377 pages of 128, the state
    class of 3 KDA layers over 193 blocks: 2.26 GiB in float32): the decode
    step updates every slot's state where it lies through ``kda_step`` (one
    call a KDA layer, the pool aliased in and out), the GQA layer goes
    through the compiled ``paged_attend``, ``gmm`` three times a layer in the
    compact walk and its overflow branch (1,536 pairs a layer against a
    prefix of 512), and nothing expert-sized, pool-sized or sized like the
    state ``S`` is copied; arguments and temporaries of either program stay
    under 14.5 GiB."""
    import dataclasses
    import json
    from pathlib import Path

    from distributed_training_guide_tpu.models import solar_open2
    from distributed_training_guide_tpu.ops import kda
    from distributed_training_guide_tpu.serve import kv_pages

    monkeypatch.setattr(kda, "resolve_interpret", lambda i: False)
    monkeypatch.setattr(
        kda, "_resolve_impl",
        lambda impl, op=None: "pallas" if impl == "auto" else impl)
    c = SOLAR_CELL
    real = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                       / "configs" / "solar-open2-ep8-l4.json").read_text())
    cfg = dataclasses.replace(
        solar_open2.PRESETS["solar-open2-250b"],
        num_layers=real["num_hidden_layers"],
        gqa_layers=tuple(real["gqa_layers"]), vocab_size=real["vocab_size"],
        experts_held=(real["experts_held_first"], real["n_routed_experts"]),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: solar_open2.init(cfg, jax.random.key(0)))
    leaves, treedef = jax.tree.flatten(params)
    pools = jax.eval_shape(lambda: kv_pages.init_pages(
        cfg, c["pages"], 128, n_state_blocks=c["slots"] + 1))
    names = ("k", "v", "seq_state", "seq_conv")
    assert pools["seq_state"].shape == (3, c["slots"] + 1, 64, 128, 128)
    # float32 beside bf16 weights, k and v: the state's precision is not the
    # pool's (no comparison of served tokens would see it narrower)
    assert pools["seq_state"].dtype == jnp.float32 \
        and pools["k"].dtype == pools["seq_conv"].dtype == jnp.bfloat16
    slots, t = (c["slots"], 1) if program == "decode" else (1, c["chunk"])

    def step(kp, vp, sp, cp, ids, lengths, tables, *flat):
        logits, cache = solar_open2.paged_decode_step(
            cfg, jax.tree.unflatten(treedef, flat), ids, lengths,
            dict(zip(names, (kp, vp, sp, cp))),
            kv_pages.make_attend(tables, lengths, impl="flash",
                                 n_valid=jnp.full((slots,), t),
                                 state_class=True),
            last_index=jnp.asarray(t - 1))
        return (jnp.argmax(logits, -1), *(cache[n] for n in names),
                cache["routing"])

    specs = [(pools[n].shape, pools[n].dtype) for n in names] + [
        ((slots, t), jnp.int32), ((slots,), jnp.int32),
        ((slots, c["columns"] + 1), jnp.int32)] + [
        (x.shape, x.dtype) for x in leaves]
    compiled = jax.jit(step, donate_argnums=(0, 1, 2, 3)).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in specs)).compile()
    text = compiled.as_text()
    calls = kernel_calls(text)
    assert sum(named(x, "gmm") for x in calls) == 2 * 3 * 4, calls
    assert sum(named(x, "paged_attend") for x in calls) == 1, calls
    assert sum(named(x, "kda_step") for x in calls) == (
        3 if program == "decode" else 0), calls
    # a chunk's recurrence is ONE kernel a KDA layer: no triangular solve,
    # and no pairwise decay written out over [.., block, block, d_k]
    assert sum(named(x, "kda_chunk") for x in calls) == (
        0 if program == "decode" else 3), calls
    assert "triangular" not in text
    assert not re.search(rf"f32\[[\d,]*{kda.BLOCK},{kda.BLOCK},128\]", text)
    # k, v and S. The conv leaf (85 MB: three rows a block tile badly) is
    # re-laid by the compiler four times a decode step (1.2 ms on the chip);
    # a flat leaf compiled without them and ran 6 ms SLOWER (its scatter
    # became a loop over the slots): PERF.md section 6, PR 44
    sized = pool_sized_ops(text, *(pools[n].shape for n in names[:3]),
                           names=True)
    # (the chunk's one slot writes its state back by an in-place
    # `dynamic-update-slice`, the decode step's 192 through the kernel)
    in_place = ("parameter", "bitcast", "scatter", "get-tuple-element",
                "fusion", "custom-call", "tuple", "dynamic-update-slice")
    moved = [x for x in sized
             if x.split()[0] not in in_place or "copy" in x
             or ("slice" in x and x.split()[0] != "dynamic-update-slice")]
    assert not moved, moved
    assert_experts_read_in_place(
        text, *(params["layers"]["moe"][leaf].shape
                for leaf in ("gate", "up", "down")))
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes >= sum(
        math.prod(pools[n].shape) * pools[n].dtype.itemsize for n in names)
    assert held < 14.5 * 2 ** 30, held / 2 ** 30


# ---- the state-space family's cell -------------------------------------------
# jamba2-3b.serve.chat256: the WHOLE model (28 layers, 65,536 rows), 256 slots
# of 20 query heads on ONE kv head, page 128, 12 table columns, 3,073 pages,
# 257 state blocks of 26 Mamba layers, a chunk of 1,024

JAMBA_CELL = dict(slots=256, columns=12, pages=3073, chunk=1024)


@pytest.mark.parametrize("slots,t", [(256, 1), (1, 1024)],
                         ids=["decode", "chunk1024"])
def test_paged_attend_compiles_at_20_query_heads_on_one_kv_head(
        chip_compile, slots, t):
    """A decode query block of 20 rows (not a whole sublane tile of 8s per
    kv head as 4, 8 or 16 a group are) and a k page of 128 tokens x 1 head
    x 128 = 32 KB."""
    c = JAMBA_CELL
    pool = ((2, c["pages"], 128, 1, D), jnp.bfloat16)
    text = chip_compile(
        lambda *a: paged_flash_attend(*a, interpret=False),
        ((slots, t, 20, D), jnp.bfloat16), pool, pool, LAYER,
        ((slots, c["columns"]), jnp.int32), ((slots,), jnp.int32))
    calls = kernel_calls(text)
    assert len(calls) == 1 and named(calls[0], "paged_attend"), calls
    # (the chunk's call sits in a loop over query blocks: its tuple too)
    assert set(pool_sized_ops(text, pool[0])) <= {
        "bitcast", "parameter", "get-tuple-element", "tuple", "while"}


@pytest.mark.parametrize("program", ["decode", "chunk1024"])
def test_state_space_familys_serve_programs_compile_at_the_cells_size(
        one_chip, chip_compile, compiled_kernels, monkeypatch, program):
    """``jamba2-3b.serve.chat256``'s decode step (256 slots) and prefill
    chunk (1,024 tokens), whole, at the cell's size (5.64 GiB of weights, k
    and v of the two attention layers over 3,073 pages of 128, the state
    class of 26 Mamba layers over 257 blocks: 2.04 GiB in float32 and 0.19
    of conv rows): the decode step updates every slot's state where it lies
    through ``ssm_step`` (one call a Mamba layer, the pool aliased in and
    out), a chunk scans each Mamba layer through ONE ``ssm_chunk`` kernel,
    the two attention layers go through the compiled ``paged_attend``, and
    nothing weight-sized, pool-sized or state-class-sized is copied;
    arguments and temporaries of either program stay under 14.5 GiB."""
    import dataclasses

    from distributed_training_guide_tpu.models import jamba
    from distributed_training_guide_tpu.ops import ssm
    from distributed_training_guide_tpu.serve import kv_pages

    monkeypatch.setattr(ssm, "resolve_interpret", lambda i: False)
    monkeypatch.setattr(
        ssm, "_resolve_impl",
        lambda impl, op=None: "pallas" if impl == "auto" else impl)
    c = JAMBA_CELL
    cfg = dataclasses.replace(jamba.PRESETS["jamba2-3b"], dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: jamba.init(cfg, jax.random.key(0)))
    leaves, treedef = jax.tree.flatten(params)
    pools = jax.eval_shape(lambda: kv_pages.init_pages(
        cfg, c["pages"], 128, n_state_blocks=c["slots"] + 1))
    names = ("k", "v", "seq_state", "seq_conv")
    assert pools["seq_state"].shape == (26, c["slots"] + 1, 16, 5120)
    assert pools["seq_conv"].shape == (26, c["slots"] + 1, 3, 5120)
    assert pools["k"].shape == (2, c["pages"], 128, 1, 128)
    # float32 beside bf16 weights, k and v: the state's precision is not the
    # pool's
    assert pools["seq_state"].dtype == jnp.float32 \
        and pools["k"].dtype == pools["seq_conv"].dtype == jnp.bfloat16
    slots, t = (c["slots"], 1) if program == "decode" else (1, c["chunk"])

    def step(kp, vp, sp, cp, ids, lengths, tables, *flat):
        logits, cache = jamba.paged_decode_step(
            cfg, jax.tree.unflatten(treedef, flat), ids, lengths,
            dict(zip(names, (kp, vp, sp, cp))),
            kv_pages.make_attend(tables, lengths, impl="flash",
                                 n_valid=jnp.full((slots,), t),
                                 state_class=True),
            last_index=jnp.asarray(t - 1))
        return (jnp.argmax(logits, -1), *(cache[n] for n in names))

    specs = [(pools[n].shape, pools[n].dtype) for n in names] + [
        ((slots, t), jnp.int32), ((slots,), jnp.int32),
        ((slots, c["columns"] + 1), jnp.int32)] + [
        (x.shape, x.dtype) for x in leaves]
    compiled = jax.jit(step, donate_argnums=(0, 1, 2, 3)).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in specs)).compile()
    text = compiled.as_text()
    calls = kernel_calls(text)
    assert sum(named(x, "paged_attend") for x in calls) == 2, calls
    assert sum(named(x, "ssm_step") for x in calls) == (
        26 if program == "decode" else 0), calls
    assert sum(named(x, "ssm_chunk") for x in calls) == (
        0 if program == "decode" else 26), calls
    # k, v and h (the conv leaf, three rows a block, is re-laid by the
    # compiler as Solar's is: PERF.md section 6, PR 44) and the largest
    # weights: the embedding (also the head), W_in, the FFN's three
    sized = pool_sized_ops(text, *(pools[n].shape for n in names[:3]),
                           names=True)
    # (the chunk's one slot writes its state back by an in-place
    # `dynamic-update-slice`, the decode step's 256 through the kernel)
    in_place = ("parameter", "bitcast", "scatter", "get-tuple-element",
                "fusion", "custom-call", "tuple", "dynamic-update-slice")
    moved = [x for x in sized
             if x.split()[0] not in in_place or "copy" in x
             or ("slice" in x and x.split()[0] != "dynamic-update-slice")]
    assert not moved, moved
    weights = [(65536, 2560), (2560, 10240), (2560, 8192), (8192, 2560),
               (5120, 2560)]
    assert not operand_sized_moves(text, *weights, dtypes=("bf16",))
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes >= sum(
        math.prod(pools[n].shape) * pools[n].dtype.itemsize for n in names)
    assert held < 14.5 * 2 ** 30, held / 2 ** 30


# ---- the power-retention family's cell ----------------------------------------
# brumby-14b-l8.serve.doc16: 8 of 40 layers at every width, the whole untied
# vocabulary, 16 slots of 40 query heads on 8 kv heads, NO attending layer (no
# k or v leaf), 100 table columns and the block id, 17 state blocks of 8
# layers (S [8, 36, 256, 128] and Z [8, 128, 128] float32), a chunk of 1,024

BRUMBY_CELL = dict(slots=16, columns=100, chunk=1024, layers=8)


@pytest.mark.parametrize("program", ["decode", "chunk1024"])
def test_retention_familys_serve_programs_compile_at_the_cells_size(
        one_chip, chip_compile, monkeypatch, capsys, program):
    """``brumby-14b-l8.serve.doc16``'s decode step (16 slots) and prefill
    chunk (1,024 tokens), whole, at the cell's size (7.82 GiB of weights, the
    state class of 8 layers over 17 blocks: 4.85 GiB in float32, no k or v
    pool at all): the decode step updates every slot's state where it lies
    through ``retention_step`` (one call a layer, the pool aliased in and
    out), a chunk goes through ONE ``retention_chunk`` kernel a layer, also
    in place, no ``paged_attend`` exists, and nothing weight-sized or
    pool-sized is copied; arguments and temporaries of either program stay
    under 14.5 GiB, and are printed."""
    import dataclasses

    from distributed_training_guide_tpu.models import brumby
    from distributed_training_guide_tpu.ops import retention
    from distributed_training_guide_tpu.serve import kv_pages

    monkeypatch.setattr(retention, "resolve_interpret", lambda i: False)
    monkeypatch.setattr(
        retention, "_resolve_impl",
        lambda impl, op=None: "pallas" if impl == "auto" else impl)
    c = BRUMBY_CELL
    cfg = dataclasses.replace(brumby.PRESETS["brumby-14b"],
                              num_layers=c["layers"], dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    assert cfg.num_params() == 4_198_652_928
    params = jax.eval_shape(lambda: brumby.init(cfg, jax.random.key(0)))
    leaves, treedef = jax.tree.flatten(params)
    pools = jax.eval_shape(lambda: kv_pages.init_pages(
        cfg, 1 + c["slots"] * c["columns"], 128,
        n_state_blocks=c["slots"] + 1))
    names = ("seq_state", "seq_norm")
    assert set(pools) == set(names)         # no k, no v
    assert pools["seq_state"].shape == (8, c["slots"] + 1, 8, 36, 256, 128)
    assert pools["seq_norm"].shape == (8, c["slots"] + 1, 8, 128, 128)
    assert all(pools[n].dtype == jnp.float32 for n in names)
    slots, t = (c["slots"], 1) if program == "decode" else (1, c["chunk"])

    def step(sp, zp, ids, lengths, tables, *flat):
        logits, cache = brumby.paged_decode_step(
            cfg, jax.tree.unflatten(treedef, flat), ids, lengths,
            dict(zip(names, (sp, zp))),
            kv_pages.make_attend(tables, lengths, impl="flash",
                                 n_valid=jnp.full((slots,), t),
                                 state_class=True),
            last_index=jnp.asarray(t - 1))
        return (jnp.argmax(logits, -1), *(cache[n] for n in names))

    specs = [(pools[n].shape, pools[n].dtype) for n in names] + [
        ((slots, t), jnp.int32), ((slots,), jnp.int32),
        ((slots, c["columns"] + 1), jnp.int32)] + [
        (x.shape, x.dtype) for x in leaves]
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in specs)).compile()
    text = compiled.as_text()
    calls = kernel_calls(text)
    assert not any(named(x, "paged_attend") for x in calls), calls
    assert sum(named(x, "retention_step") for x in calls) == (
        8 if program == "decode" else 0), calls
    assert sum(named(x, "retention_chunk") for x in calls) == (
        0 if program == "decode" else 8), calls
    # S is carried in place through the kernels (the normaliser, 9 MB a
    # layer, is gathered and scattered by XLA in the decode step)
    sized = pool_sized_ops(text, pools["seq_state"].shape, names=True)
    in_place = ("parameter", "bitcast", "get-tuple-element", "fusion",
                "custom-call", "tuple")
    moved = [x for x in sized if x.split()[0] not in in_place or "copy" in x]
    assert not moved, moved
    # the embedding, the head and the FFN's three (W_q or W_o, 52 MB, the
    # compiler itself stages into its fast memory ahead of the product: a
    # `copy` to memory space 1, eight a decode program)
    weights = [(151936, 5120), (5120, 151936), (5120, 17408), (17408, 5120)]
    assert not operand_sized_moves(text, *weights, dtypes=("bf16",))
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    with capsys.disabled():
        print(f"\n{program}: arguments {mem.argument_size_in_bytes:,} B, "
              f"temporaries {mem.temp_size_in_bytes:,} B, held "
              f"{held / 2 ** 30:.2f} GiB")
    assert mem.alias_size_in_bytes >= sum(
        math.prod(pools[n].shape) * 4 for n in names)
    assert held < 14.5 * 2 ** 30, held / 2 ** 30


@pytest.mark.parametrize("program", ["decode", "chunk512"])
def test_moe_familys_serve_programs_read_the_experts_in_place(
        chip_compile, compiled_kernels, program):
    """``moe.paged_decode_step`` (the Mixtral / Qwen-MoE families) at
    ``qwen3-30b-a3b``'s widths cut to 4 layers, bf16 leaves: the same
    in-place read through the same code, three ``gmm`` calls a layer and
    nothing of an expert leaf's size sliced or copied."""
    import dataclasses

    from distributed_training_guide_tpu.models import moe
    from distributed_training_guide_tpu.serve import kv_pages

    cfg = dataclasses.replace(moe.PRESETS["qwen3-30b-a3b"], num_layers=4,
                              dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: moe.init(cfg, jax.random.key(0)))
    leaves, treedef = jax.tree.flatten(params)
    weights = [(x.shape, x.dtype) for x in leaves]
    pool = ((4, CELL_PAGES, CELL_PAGE, cfg.num_kv_heads, D), jnp.bfloat16)
    slots, t = (16, 1) if program == "decode" else (1, 512)

    def step(kp, vp, ids, lengths, tables, *flat):
        logits, cache = moe.paged_decode_step(
            cfg, jax.tree.unflatten(treedef, flat), ids, lengths,
            {"k": kp, "v": vp},
            kv_pages.make_attend(tables, lengths, impl="flash",
                                 n_valid=jnp.full((slots,), t)),
            last_index=jnp.asarray(t - 1))
        return jnp.argmax(logits, -1), cache["k"], cache["v"]

    text = chip_compile(step, pool, pool, ((slots, t), jnp.int32),
                        ((slots,), jnp.int32),
                        ((slots, CELL_COLUMNS), jnp.int32), *weights,
                        donate=(0, 1))
    calls = kernel_calls(text)
    assert sum(named(x, "gmm") for x in calls) == 3, calls
    assert sum(named(x, "paged_attend") for x in calls) == 1, calls
    assert_pools_carried_in_place(text, pool[0])
    assert_experts_read_in_place(
        text, *(params["layers"]["moe"][leaf].shape
                for leaf in ("gate", "up", "down")))


@pytest.mark.parametrize("program", ["decode", "chunk512"])
def test_llama_familys_serve_programs_carry_the_pools_in_place(
        chip_compile, compiled_kernels, program):
    """``olmo2-7b-l12.serve.decode16``'s decode step and prefill chunk, whole,
    at the cell's size (12 layers of 7B widths, two pools of 1344 pages of 16
    tokens, 3.94 GiB): one kernel kind (the attend), and nothing of the
    pools' size, or of one layer's, is sliced, updated by slice or copied.
    With the pools as scanned inputs and stacked outputs these programs held
    six such operations and 4.59 GiB of temporaries, a second copy of both
    pools."""
    import dataclasses

    from distributed_training_guide_tpu.models import llama
    from distributed_training_guide_tpu.serve import kv_pages

    cfg = dataclasses.replace(llama.PRESETS["olmo2-7b"], num_layers=12,
                              dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: llama.init(cfg, jax.random.key(0)))
    leaves, treedef = jax.tree.flatten(params)
    weights = [(x.shape, x.dtype) for x in leaves]
    pool = ((12, CELL_PAGES, CELL_PAGE, 32, D), jnp.bfloat16)
    slots, t = (16, 1) if program == "decode" else (1, 512)

    def step(kp, vp, ids, lengths, tables, *flat):
        logits, cache = llama.paged_decode_step(
            cfg, jax.tree.unflatten(treedef, flat), ids, lengths,
            {"k": kp, "v": vp},
            kv_pages.make_attend(tables, lengths, impl="flash",
                                 n_valid=jnp.full((slots,), t)),
            last_index=jnp.asarray(t - 1))
        return jnp.argmax(logits, -1), cache["k"], cache["v"]

    text = chip_compile(step, pool, pool, ((slots, t), jnp.int32),
                        ((slots,), jnp.int32),
                        ((slots, CELL_COLUMNS), jnp.int32), *weights,
                        donate=(0, 1))
    calls = kernel_calls(text)
    assert len(calls) == 1 and named(calls[0], "paged_attend"), calls
    assert_pools_carried_in_place(text, pool[0])


def test_fsdp_loss_head_gathers_once_at_the_four_chip_cells_shapes(
        topo, chip_compile):
    """``olmo2-7b-l8.train.fsdp4.seq4096``'s loss head (hidden 4096, vocab
    100,352, 16 chunks, 8 x 4096 tokens) under the ``fsdp`` plan on the four
    described chips, forward and backward: the output matrix is gathered
    once and its gradient reduce-scattered once, both OUTSIDE the two chunk
    loops, no collective moves the whole matrix inside a loop, nothing
    all-reduces it, the backward loop carries the partial gradient in fp32
    while both loops read the matrix in bf16 (the widened copy is never
    made), and the temporaries fit beside the cell's state."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_training_guide_tpu.ops.cross_entropy import (
        make_gathered_chunked_loss)
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.utils import hlo

    e, v, chunks, batch, seq = 4096, 100352, 16, 8, 4096
    plan = make_plan("fsdp", make_mesh(fsdp=4, devices=topo.devices))
    mesh = plan.mesh
    loss = make_gathered_chunked_loss(mesh, P("fsdp", None), plan.data_axes,
                                      num_chunks=chunks)

    @jax.named_scope("loss_head")
    def head(hidden, w_master, labels):
        return loss(hidden, w_master.astype(jnp.bfloat16), labels)

    rows = NamedSharding(mesh, P(plan.data_axes))
    shard = NamedSharding(mesh, P("fsdp"))
    compiled = jax.jit(
        jax.value_and_grad(head, argnums=(0, 1)),
        out_shardings=(plan.replicated(), (rows, shard))).lower(
        jax.ShapeDtypeStruct((batch, seq, e), jnp.bfloat16, sharding=rows),
        jax.ShapeDtypeStruct((e, v), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=rows),
    ).compile()
    text = compiled.as_text()
    lines = text.splitlines()
    assert len(re.findall(r"\swhile\(", text)) == 2     # the two chunk loops
    moved = {"all-gather": [], "reduce-scatter": [], "all-reduce": []}
    for c, in_loop in hlo.collectives_moving(text, e * v, shards=4):
        assert not in_loop, lines[c.line][:200]
        moved[c.kind].append(lines[c.line][:400])
    assert len(moved["all-gather"]) == 1, moved
    assert len(moved["reduce-scatter"]) == 1, moved
    assert not moved["all-reduce"], moved
    for line in moved["all-gather"] + moved["reduce-scatter"]:
        assert "loss_head" in line and "/head_gather/" in line, line
    carried = sorted(
        sorted(set(re.findall(rf"(\w+)\[{e},{v}\]", l.split(" while(")[0])))
        for l in lines if " while(" in l)
    assert carried == [["bf16"], ["bf16", "f32"]], carried
    assert not [l for l in lines if f" = f32[{e},{v}]" in l
                and "/head_gather/" in l and "transpose(" not in l], "widened"
    # 2.44 B parameters x 16 B (fp32 weights, two moments, gradients) over
    # four chips, and what this program plans beside them
    params = 2 * e * v + 8 * (4 * e * e + 3 * e * 11008)
    state_gib = params * 16 / 4 / 2**30
    temp_gib = compiled.memory_analysis().temp_size_in_bytes / 2**30
    assert temp_gib < 3.5, temp_gib
    assert state_gib + temp_gib < 15.75, (state_gib, temp_gib)


def test_fsdp_heads_reduce_scatter_runs_before_the_layers_backward(
        topo, chip_compile):
    """A whole FSDP step on the four described chips (a head of 512 x
    32,768, four layers): the chip's scheduler, left alone, puts the head's
    one reduce-scatter AFTER the layers' backward loop and so holds the
    whole-matrix fp32 partial gradient through it (at the four-chip cell's
    size 15.50 GiB planned where 12.91 are needed);
    ``_cotangents_together`` makes that loop wait for it."""
    import optax

    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.train import Trainer
    from distributed_training_guide_tpu.train.step import lower_step

    trainer = Trainer(
        bundle=get_model("llama-debug", tie_word_embeddings=False,
                         hidden_size=512, vocab_size=32768, num_layers=4),
        optimizer=optax.adamw(1e-3), loss_chunks=4, remat=True,
        attn_impl="xla",
        plan=make_plan("fsdp", make_mesh(fsdp=4, devices=topo.devices)))
    assert trainer.head_gather["once"]
    lowered, _ = lower_step(trainer, global_batch=8, seq_length=128)
    text = lowered.compile().as_text()
    entry = text[text.index("\nENTRY"):].splitlines()
    at = {what: next(i for i, l in enumerate(entry)
                     if f" {op}(" in l and f'{what}"' in l)
          for op, what in (
              ("while", "transpose(jvp(loss_head))/shard_map/while"),
              ("reduce-scatter", "head_gather/reduce_scatter"),
              ("while", "transpose(jvp(layers))/while"))}
    assert list(at.values()) == sorted(at.values()), at


def test_sparse_train_cells_step_compiles_at_the_cells_size(
        topo, chip_compile, compiled_kernels, monkeypatch):
    """``laguna-xs.2-ep8-l5.train.seq8192``'s whole train step, built from the
    cell's own files as its runner builds it (``single`` plan, AdamW, fp32
    parameters, remat ``all``, 16 loss chunks, batch 2 x 8192), for one
    described chip: the 691,623,936 parameters held with their two moments
    (arguments, 7.73 GiB) and the temporaries beside them (5.90) fit the
    chip's 15.75 GiB. Kernel calls, by the reckoning: a layer's attention is
    ``flash_fwd`` in the forward, ``flash_fwd`` again under remat,
    ``flash_dq`` and ``flash_dkv`` in the backward, 5 layers; a sparse
    layer's experts are 3 ``gmm`` in the forward, 3 rematted, 3 against the
    transposed matrices and 3 ``tgmm`` in the backward, 4 layers, ONCE FOR
    EACH BRANCH of the dispatch's ``cond`` (``moe._ragged_dispatch``: the
    compact walk of 32,768 sorted rows with its scatter-add into ``[16384,
    2048]``, and the full-width walk of 131,072 that an overflow takes; a
    step runs one of them): 72 and 24. No fp32 expert leaf (a parameter or a
    moment, 134 MB each) is copied: every ``copy`` of a layer's ``[32, 2048,
    512]`` is of the bf16 cast the kernels read (at most 4 in a branch of a
    sparse layer's backward, 7 in both)."""
    import json
    from pathlib import Path

    from benchmarks import harness
    from benchmarks.runners import _laguna
    from distributed_training_guide_tpu.ops import flash_attention as fa
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.train import Trainer
    from distributed_training_guide_tpu.train.optimizer import OPTIMIZERS
    from distributed_training_guide_tpu.train.step import lower_step

    monkeypatch.setattr(fa, "resolve_interpret", lambda i: False)
    root = Path(__file__).resolve().parents[1]
    cell = harness.load_cell(json.loads((root / "BENCHMARK.json").read_text()),
                             "laguna-xs.2-ep8-l5.train.seq8192")
    cfg, job, mix = cell["config_data"], cell["job"], cell["traffic_data"]
    opt = dict(job["optimizer"])
    trainer = Trainer(
        bundle=_laguna.bundle_for(cfg, cell["config"]),
        optimizer=OPTIMIZERS[opt.pop("name")](opt.pop("lr"), **opt),
        plan=make_plan(job["plan"]["strategy"],
                       make_mesh(devices=topo.devices[:1])),
        remat=job["remat"], remat_policy=job["remat_policy"],
        loss_chunks=job["loss_chunks"], attn_impl="flash",
        precision=job["precision"])
    lowered, _ = lower_step(trainer, global_batch=mix["global_batch"],
                            seq_length=mix["seq_len"])
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = 691_623_936
    assert memory.argument_size_in_bytes >= 12 * held
    gib = (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / 2**30
    assert gib < 15.75, gib
    text = compiled.as_text()
    calls = kernel_calls(text)
    count = lambda name: sum(named(x, name) for x in calls)
    assert (count("gmm"), count("tgmm")) == (2 * 36, 2 * 12), calls
    assert (count("flash_fwd"), count("flash_dq"), count("flash_dkv")) == (
        10, 5, 5), calls
    copies = re.findall(r"= (\w+)\[32,(?:2048,512|512,2048)\]\S* copy\(",
                        text)
    assert set(copies) <= {"bf16"} and len(copies) <= 7 * 4, copies
    assert len(re.findall(r" conditional\(", text)) == 3 * 4
