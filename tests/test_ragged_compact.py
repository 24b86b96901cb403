"""The ragged dispatch over a held share walks a static prefix of the sorted
pairs (``models/moe.py``: ``compact_rows``, ``rows_walked``,
``_ragged_dispatch``), on the CPU in float32: the compact walk against the
full-width walk and a dense reference written here, an overflow that must
fall back to the full width and lose nothing, the counter on the step's
metrics, and the programs the static rule must leave as they were."""
import dataclasses
import importlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from distributed_training_guide_tpu.models import laguna, moe  # noqa: E402
from distributed_training_guide_tpu.models.registry import get_model  # noqa: E402
from distributed_training_guide_tpu.parallel import make_mesh, make_plan  # noqa: E402
from distributed_training_guide_tpu.train import Trainer  # noqa: E402

gmm_mod = importlib.import_module(
    "distributed_training_guide_tpu.ops.grouped_matmul")

D, F, EX, K, T = 32, 16, 16, 2, 512      # k T = 1,024 pairs, two row tiles
LEAVES = ("router", "gate", "up", "down")
# float32 both ways, sums in another order (tests/test_laguna.py's)
OUT_TOL, GRAD_TOL = 3e-5, 2e-5


@dataclasses.dataclass(frozen=True)
class HeldConfig(moe.MoELlamaConfig):
    experts_held: tuple = None


def config_for(first, held, **over):
    return HeldConfig(hidden_size=D, intermediate_size=F, num_experts=EX,
                      experts_per_token=K, experts_held=(first, held),
                      moe_dispatch="ragged", dtype=jnp.float32, **over)


def layer_for(held, seed=0):
    keys = jax.random.split(jax.random.key(seed), 6)
    normal = jax.random.normal
    leaves = {"router": normal(keys[0], (D, EX)),
              "gate": 0.3 * normal(keys[1], (held, D, F)),
              "up": 0.3 * normal(keys[2], (held, D, F)),
              "down": 0.3 * normal(keys[3], (held, F, D))}
    return (leaves, normal(keys[4], (1, T, D)), normal(keys[5], (T, D)))


def full_width(monkeypatch):
    """The dispatch with the compact walk out of it: every pair, always."""
    monkeypatch.setattr(moe, "compact_rows",
                        lambda config, t: config.experts_per_token * t)


def dense_reference(config, x, leaves):
    """Every held expert on every token, weighted by the router's choice of
    it (zero where it was not chosen): no sort, no grouped product."""
    xt = x[0]
    probs = jax.nn.softmax(xt @ leaves["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, config.experts_per_token)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    first, held = moe.experts_held(config)
    y = jnp.zeros_like(xt)
    for j in range(held):
        w = jnp.sum(jnp.where(top_i == first + j, top_p, 0.0), axis=-1)
        h = jax.nn.silu(xt @ leaves["gate"][j]) * (xt @ leaves["up"][j])
        y = y + w[:, None] * (h @ leaves["down"][j])
    return y[None], None


def value_and_grads(fn, config, x, leaves, r):
    """(y, counts or None, d<y, r> / d(x, every leaf)) of ``fn -> (y,
    counts)``."""
    def scalar(x, leaves):
        y, counts = fn(config, x, leaves)
        return jnp.sum(y[0] * r), (y, counts)
    (_, (y, counts)), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=(0, 1), has_aux=True))(x, leaves)
    return y, counts, grads


def program(config, x, leaves):
    y, _, _, counts = moe._moe_ffn(config, x, leaves, return_counts=True)
    return y, counts


def assert_same(got, want):
    (y, _, (dx, dl)), (wy, _, (wdx, wdl)) = got, want
    assert float(jnp.max(jnp.abs(y - wy))) < OUT_TOL
    assert float(jnp.max(jnp.abs(wy))) > 1e-2
    for name, g, w in [("x", dx, wdx)] + [(n, dl[n], wdl[n])
                                          for n in LEAVES]:
        scale = max(float(jnp.max(jnp.abs(w))), 1e-3)
        assert float(jnp.max(jnp.abs(g - w))) < GRAD_TOL * scale + 1e-7, name
        assert float(jnp.max(jnp.abs(w))) > 0, name


@pytest.mark.parametrize("first,held,gmm", [
    (4, 4, "scan"), (6, 2, "scan"), (3, 1, "scan"), (6, 2, "pallas")])
def test_compact_walk_matches_full_width_and_dense_reference(
        first, held, gmm, monkeypatch):
    """Shares of 1/4, 1/8 and 1/16 that do not begin at expert 0: output and
    the gradients of the rows, the router and the three expert leaves; the
    group scan and the interpreted ``gmm`` / ``tgmm`` kernels over the
    ``[rows, D]`` buffer."""
    if gmm == "pallas":
        monkeypatch.setattr(gmm_mod, "_resolve_impl",
                            lambda impl: "pallas" if impl == "auto" else impl)
    moe._walk_jit.clear_cache()    # traced once a shape, whatever the impl
    config = config_for(first, held)
    leaves, x, r = layer_for(held, seed=first)
    rows = moe.compact_rows(config, T)
    assert rows == 512 < K * T
    compact = value_and_grads(program, config, x, leaves, r)
    counts = np.asarray(compact[1])
    assert counts.shape == (4,) and counts.dtype == np.int32
    assert counts[0] == K * T and 0 < counts[1] <= rows
    assert int(moe.rows_walked(config, T, counts[1])) == rows
    assert_same(compact, value_and_grads(dense_reference, config, x, leaves,
                                         r))
    full_width(monkeypatch)
    full = value_and_grads(program, config, x, leaves, r)
    assert np.array_equal(np.asarray(full[1]), counts)
    assert_same(compact, full)


def test_an_overflow_walks_every_pair_and_drops_none(monkeypatch):
    """A router that sends both choices of every token to the four held
    experts: 1,024 held pairs against a prefix of 512, so the step takes the
    full-width branch; nothing is dropped and ``rows_walked`` says ``k T``."""
    config = config_for(4, 4)
    leaves, x, r = layer_for(4, seed=9)
    push = jnp.zeros((EX,)).at[4:8].set(30.0)
    leaves["router"] = 0.1 * leaves["router"] + push / D
    x = x + 1.0                     # rows of mean 1: the push decides
    over = value_and_grads(program, config, x, leaves, r)
    counts = np.asarray(over[1])
    assert counts[1] == K * T > moe.compact_rows(config, T)
    assert int(moe.rows_walked(config, T, counts[1])) == K * T
    assert_same(over, value_and_grads(dense_reference, config, x, leaves, r))
    full_width(monkeypatch)
    assert_same(over, value_and_grads(program, config, x, leaves, r))


def test_the_rule_is_static_in_the_shapes_and_the_share():
    """``compact_rows``: twice the even share, to a row tile of 512, at most
    ``k T``; the cell's, the two chunk programs' and the decode steps'."""
    cut = laguna.PRESETS["laguna-xs.2-ep8-l5"]
    assert moe.compact_rows(cut, 2 * 8192) == 32768
    assert moe.compact_rows(laguna.PRESETS["laguna-xs.2"], 16384) == 131072
    mistral = HeldConfig(num_experts=128, experts_per_token=4,
                         experts_held=(0, 32))
    mimo = HeldConfig(num_experts=256, experts_per_token=8,
                      experts_held=(0, 16))
    assert (moe.compact_rows(mistral, 2048), moe.compact_rows(mistral, 32)
            ) == (4096, 128)
    assert (moe.compact_rows(mimo, 2048), moe.compact_rows(mimo, 64)
            ) == (2048, 512)
    walked = moe.rows_walked(cut, 16384, jnp.asarray([16400, 32768, 32769]))
    assert walked.dtype == jnp.int32
    assert walked.tolist() == [32768, 32768, 131072]


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_rows_walked_rides_the_steps_metrics(grad_accum):
    """``laguna-debug`` (2 of 8 experts held, top-2, 3 sparse layers) at 2 x
    512 tokens a microbatch: ``k T`` = 2,048 pairs a layer, prefix 1,024;
    summed over the layers and over the microbatches."""
    bundle = get_model("laguna-debug", dtype=jnp.float32)
    assert laguna.TRAIN_METRICS["moe_rows_walked"] == "sum"
    trainer = Trainer(bundle=bundle, optimizer=optax.adamw(3e-3), remat=True,
                      loss_chunks=4, attn_impl="xla", grad_accum=grad_accum,
                      plan=make_plan("single",
                                     make_mesh(devices=jax.devices()[:1])))
    state = trainer.init_state(0)
    shape = (2, 512) if grad_accum == 1 else (grad_accum, 2, 512)
    ids = jax.random.randint(jax.random.key(1), shape, 0, 512)
    _, metrics = trainer.step_fn(state, {"input_ids": ids, "labels": ids})
    assert moe.compact_rows(bundle.config, 1024) == 1024
    assert metrics["moe_rows_walked"].dtype == jnp.int32
    assert int(metrics["moe_pairs_routed"]) == grad_accum * 3 * 2048
    assert 0 < int(metrics["moe_pairs_held"]) < grad_accum * 3 * 1024
    assert int(metrics["moe_rows_walked"]) == grad_accum * 3 * 1024
    assert np.isfinite(float(metrics["loss"]))


def serve_config(name):
    """The benchmark's serve configuration ``name`` as its adapter builds
    it, and the ``[slots, T]`` of its decode step."""
    import importlib

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == name)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    adapter = importlib.import_module(f"benchmarks.runners._{cfg['family']}")
    cell = next(w for w in bench["workloads"] if w["config"] == name)
    job = json.loads((ROOT / "benchmarks" / "workloads"
                      / f"{cell['name']}.json").read_text())
    return adapter.bundle_for(cfg, name).config, job["engine"]["n_slots"]


@pytest.mark.parametrize("name", [
    "mistral-small-4-ep4-l6", "mimo-v2.5-ep16-l7", "lfm2-24b-a2b-l9",
    "all-held-train"])
def test_the_static_rule_keeps_the_programs_that_walk_every_pair(
        name, monkeypatch):
    """A decode step's ``k T`` is within one row tile and an all-held layer's
    share is the whole: ``_moe_ffn`` lowers to the same text with the compact
    walk taken out of the module."""
    if name == "all-held-train":
        config, shape, wide = config_for(0, EX), (2, T), F
    else:
        config, slots = serve_config(name)
        shape = (slots, 1)
        wide = getattr(config, "moe_intermediate_size",
                       config.intermediate_size)
    d, ex = config.hidden_size, config.num_experts
    held = moe.experts_held(config)[1]
    t = shape[0] * shape[1]
    assert moe.compact_rows(config, t) == config.experts_per_token * t
    cdt = config.dtype
    spec = jax.ShapeDtypeStruct
    leaves = {"router": spec((d, ex), jnp.float32),
              "gate": spec((held, d, wide), cdt),
              "up": spec((held, d, wide), cdt),
              "down": spec((held, wide, d), cdt)}
    if getattr(config, "router_act", "softmax") == "sigmoid":
        leaves["router_bias"] = spec((ex,), jnp.float32)

    def lowered():
        def fn(x, leaves):
            return moe._moe_ffn(config, x, leaves, no_drop=True,
                                return_counts=True)
        return jax.jit(fn).lower(spec((*shape, d), cdt), leaves).as_text()

    as_it_is = lowered()
    full_width(monkeypatch)
    assert lowered() == as_it_is
    assert "stablehlo.case" not in as_it_is and "stablehlo.if" not in as_it_is
