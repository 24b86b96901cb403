"""The plain reference (benchmarks/reference/decoder.py) against
``models/llama.py`` at debug width on the CPU, for both block wirings, and the
control that the written tolerance has to refuse."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import weights  # noqa: E402
from benchmarks.reference import decoder as ref  # noqa: E402
from benchmarks.runners import _llama  # noqa: E402

DEBUG = Path(__file__).resolve().parent / "debug" / "configs"
# float32 program against float32 reference: only summation order differs
LOGIT_TOL = 2e-4
LOSS_TOL = 1e-5


def load(name):
    return json.loads((DEBUG / f"{name}.json").read_text())


def tokens_for(cfg, seed=0, batch=2, seq=48):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq)).astype(np.int32)


def program_logits(cfg, w, tokens, dtype):
    from distributed_training_guide_tpu.models import llama

    cfg = dict(cfg, compute_dtype=dtype, weights_dtype="float32")
    bundle = _llama.bundle_for(cfg, "debug")
    return llama.apply(bundle.config, _llama.to_program(w), jnp.asarray(tokens))


@pytest.mark.parametrize("name", ["debug-qwen3", "debug-olmo2"])
def test_reference_matches_program_logits_and_loss(name):
    cfg = load(name)
    w = weights.stacked_weights(cfg, weights.seed_key(3), jnp.float32)
    tokens = tokens_for(cfg)
    want = ref.forward_logits(cfg, w, jnp.asarray(tokens))
    got = program_logits(cfg, w, tokens, "float32")
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL

    from distributed_training_guide_tpu.ops.cross_entropy import causal_lm_loss

    nll, count = ref.nll_sum(cfg, w, jnp.asarray(tokens))
    assert float(nll / count) == pytest.approx(
        float(causal_lm_loss(got, jnp.asarray(tokens))), rel=LOSS_TOL)


@pytest.mark.parametrize("name", ["debug-qwen3", "debug-olmo2"])
def test_lower_precision_program_fails_the_tolerance(name):
    """The control: the same program computing in bfloat16 lands outside the
    tolerance that the float32 program meets."""
    cfg = load(name)
    w = weights.stacked_weights(cfg, weights.seed_key(3), jnp.float32)
    tokens = tokens_for(cfg)
    want = ref.forward_logits(cfg, w, jnp.asarray(tokens))
    got = program_logits(cfg, w, tokens, "bfloat16").astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - want))) > 3 * LOGIT_TOL


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_lowered_reference_moves_the_logits(mode):
    cfg = load("debug-olmo2")
    w = weights.stacked_weights(cfg, weights.seed_key(5), jnp.float32)
    tokens = jnp.asarray(tokens_for(cfg))
    want = ref.forward_logits(cfg, w, tokens)
    got = ref.forward_logits(cfg, w, tokens, mode)
    assert float(jnp.max(jnp.abs(got - want))) > 3 * LOGIT_TOL


def test_layer_at_a_time_equals_the_stacked_scan():
    cfg = load("debug-olmo2")
    key = weights.seed_key(2**31 + 11)
    w = weights.stacked_weights(cfg, key, jnp.float32)
    tokens = tokens_for(cfg, batch=1, seq=40)
    logits = ref.forward_logits(cfg, w, jnp.asarray(tokens))[0]
    served = np.asarray(jnp.argmax(logits, -1))          # greedy continuation
    seq = np.concatenate([tokens[0, :20], served[19:39]])  # teacher-forced mix
    top = weights.top_weights(cfg, key, jnp.float32)
    gaps = ref.served_token_gaps(
        cfg, lambda l: weights.layer_weights(cfg, key, l, jnp.float32), top,
        seq, 20)
    assert gaps.shape == (20,)
    assert gaps[0] == 0.0          # the first served token IS the argmax
    assert np.all(gaps >= 0)


def test_served_gap_catches_an_altered_token_and_the_int8_control():
    cfg = load("debug-olmo2")
    key = weights.seed_key(9)
    top = weights.top_weights(cfg, key, jnp.float32)
    layer_fn = lambda l: weights.layer_weights(cfg, key, l, jnp.float32)
    rng = np.random.default_rng(1)
    seq = list(rng.integers(0, cfg["vocab_size"], 24))
    w = weights.stacked_weights(cfg, key, jnp.float32)
    for _ in range(8):             # greedy decode with the reference itself
        padded = jnp.asarray([seq + [0] * (32 - len(seq))], jnp.int32)
        seq.append(int(jnp.argmax(
            ref.forward_logits(cfg, w, padded)[0, len(seq) - 1])))
    clean = ref.served_token_gaps(cfg, layer_fn, top, np.asarray(seq), 24)
    assert float(clean.max()) == 0.0
    broken = list(seq)
    broken[28] = (broken[28] + 1) % cfg["vocab_size"]
    assert float(ref.served_token_gaps(cfg, layer_fn, top, np.asarray(broken),
                                       24).max()) > 0.05
    control = ref.served_token_gaps(cfg, layer_fn, top, np.asarray(seq), 24,
                                    control_mode="int8")
    assert control.shape == clean.shape and np.all(control >= 0)


def test_train_steps_follow_adamw():
    """Two reference steps: the loss is finite, the first gradient's norms
    are positive, the parameters move by about lr per element (Adam)."""
    cfg = load("debug-qwen3")
    opt = {"lr": 1e-3, "t_max": 1000, "eta_min_ratio": 0.01,
           "weight_decay": 0.01, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    make = lambda key: weights.stacked_weights(cfg, key, jnp.float32)
    batches = [jnp.asarray(tokens_for(cfg, seed=s, batch=4, seq=32))
               for s in (1, 2)]
    out = ref.train_steps(cfg, opt, make, weights.seed_key(1), batches,
                          rows_per_block=2)
    assert len(out["losses"]) == 2 and all(np.isfinite(out["losses"]))
    assert all(np.all(v > 0) for v in out["grad_norms"].values())
    gate = out["delta_norms"]["layers/gate"]
    per_element = gate / np.sqrt(cfg["hidden_size"] * cfg["intermediate_size"])
    assert np.all(per_element > 0.5e-3) and np.all(per_element < 2.5e-3)


def test_weights_same_alone_stacked_and_for_large_seeds():
    cfg = load("debug-qwen3")
    key = weights.seed_key(2**31 + 12345)
    stacked = jax.jit(lambda: weights.stacked_weights(cfg, key))()
    one = jax.jit(lambda l: weights.layer_weights(cfg, key, l))(np.uint32(1))
    for name, leaf in one.items():
        assert np.array_equal(np.asarray(stacked["layers"][name][1]),
                              np.asarray(leaf)), name
    other = weights.stacked_weights(cfg, weights.seed_key(2**31 + 12346))
    assert not np.array_equal(np.asarray(other["layers"]["gate"]),
                              np.asarray(stacked["layers"]["gate"]))
    gate = np.asarray(stacked["layers"]["gate"])
    assert abs(gate.std() - weights.MATRIX_STD) < 1e-3 and abs(gate.mean()) < 1e-3
    assert weights.num_params(cfg) == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(stacked))



@pytest.mark.parametrize("config", ["debug-olmo2", "debug-qwen3", "debug-mla-moe"])
def test_a_seed_makes_the_same_numbers_as_before_it_became_an_operand(config):
    """``debug/weights_digest.json`` was taken at the parent of the PR that
    made the seed an operand of the weight programs (it was a constant baked
    into them): every leaf's bytes are what they were, and a second seed runs
    the program compiled for the first."""
    import hashlib

    from benchmarks.runners import serve

    stored = json.loads((DEBUG.parent / "weights_digest.json").read_text())
    cfg = load(config)
    mod = serve.family_of(cfg).weights
    make = jax.jit(lambda key: mod.stacked_weights(cfg, key))
    tree = make(mod.seed_key(stored["seed"]))
    got = {f"{group}/{leaf}": hashlib.sha256(np.asarray(
        x.astype(jnp.float32)).tobytes()).hexdigest()[:16]
        for group in ("top", "layers") for leaf, x in tree[group].items()}
    assert got == stored["configs"][config]
    other = make(mod.seed_key(stored["seed"] + 1))
    assert make._cache_size() == 1
    assert not np.array_equal(np.asarray(other["top"]["embed"]),
                              np.asarray(tree["top"]["embed"]))
