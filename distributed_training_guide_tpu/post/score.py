"""Pluggable rollout scorers: programmatic rewards, reward-model
forwards, and teacher-logit distillation.

One interface (``Scorer.score(rollouts) -> [Score]``) behind which the
three post-training reward shapes live:

- ``ProgrammaticScorer`` — a host function of (prompt_ids,
  generated_ids); what the synthetic-preference tasks of the tests
  use, and the shape real rule-based rewards (length penalties,
  format checks, unit tests) take.
- ``RewardModelScorer`` — a model forward as the reward: the mean
  log-probability the scoring model assigns to the sampled continuation
  (a sequence-level likelihood reward). The scoring model rides a
  ``ModelPrograms`` (or a raw (bundle, params) pair), so a post-training
  fleet can point the scorer at an already-resident serving engine's
  params without a second copy.
- ``TeacherScorer`` — full-vocab teacher log-probs at every continuation
  position, for the ``distill_kl`` objective (on-policy distillation:
  the student's own rollouts, scored by the teacher's distribution).
  Also reports the teacher's mean token log-prob as the scalar reward so
  reward trajectories stay comparable across scorer kinds.

Both model scorers compile ONE forward per padded sequence bucket
(powers of two), so scoring cost is a fixed number of programs however
ragged the rollouts are.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import numpy as np

from .rollout import Rollout, pad_bucket


@dataclasses.dataclass
class Score:
    """One rollout's score: always a scalar reward; teacher scorers add
    per-continuation-token full-vocab log-probs [len(generated), V]."""
    reward: float
    teacher_logprobs: Optional[np.ndarray] = None


class Scorer:
    """Interface: ``score(rollouts)`` returns one ``Score`` per rollout,
    in order. ``provides_teacher_logprobs`` advertises whether the
    ``distill_kl`` objective can run on this scorer's output."""

    provides_teacher_logprobs = False

    def score(self, rollouts: Sequence[Rollout]) -> list:
        raise NotImplementedError


class ProgrammaticScorer(Scorer):
    """Reward = ``fn(prompt_ids, generated_ids) -> float``."""

    def __init__(self, fn: Callable):
        self.fn = fn

    def score(self, rollouts):
        return [Score(reward=float(self.fn(r.prompt_ids, r.generated_ids)))
                for r in rollouts]


def match_reward(target_id: int):
    """Sparse synthetic preference: reward = fraction of generated
    tokens equal to ``target_id`` (~1/vocab at init — a hard
    exploration task; ``band_reward`` is the dense variant the tests
    actually learn on)."""
    def fn(prompt_ids, generated_ids):
        if not generated_ids:
            return 0.0
        return sum(1 for t in generated_ids if t == target_id) \
            / len(generated_ids)
    return fn


def band_reward(max_id: int):
    """The DENSE synthetic preference task (the tests' and the post
    CLI's default): reward = fraction of generated tokens
    with id < ``max_id``. At a random init the rate is ~max_id/vocab, so
    every rollout carries signal and REINFORCE-with-baseline moves the
    reward measurably within a few iterations on a debug model —
    deterministic, model-free, and sensitive enough to catch a broken
    mask or a stale publish (a loop that trains but never publishes
    plateaus: rollouts keep sampling the old policy)."""
    def fn(prompt_ids, generated_ids):
        if not generated_ids:
            return 0.0
        return sum(1 for t in generated_ids if t < max_id) \
            / len(generated_ids)
    return fn


class _ModelForward:
    """Shared machinery of the model-backed scorers: one jitted
    tokens -> per-position log-prob forward per power-of-two padded
    length, against a ModelPrograms' params (or a raw bundle+params)."""

    def __init__(self, model, params=None):
        import jax

        if params is None:      # a ModelPrograms: score the LIVE params
            # hold the programs object, not a snapshot of .params — a
            # publish rebinds ModelPrograms.params, and a scorer frozen
            # at construction would keep scoring with (and keep ALIVE)
            # the superseded pre-publish weights forever
            self._programs = model
            self.bundle = model.bundle
        else:
            self._programs = None
            self.bundle = model
            self._static_params = params
        self.config = self.bundle.config
        cfg, apply = self.config, self.bundle.apply

        def fwd(params, tokens):
            import jax.numpy as jnp

            logits = apply(cfg, params, tokens)
            return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

        self._fwd = jax.jit(fwd)

    @property
    def params(self):
        return (self._programs.params if self._programs is not None
                else self._static_params)

    def token_logprobs(self, rollouts):
        """Per-rollout (token_lp [g], full_lp [g, V]): the scoring
        model's log-prob of each SAMPLED continuation token, and its
        full distribution at that token's source position."""
        lens = [len(r.prompt_ids) + len(r.generated_ids) for r in rollouts]
        s = pad_bucket(max(lens))
        tokens = np.zeros((len(rollouts), s), np.int32)
        for i, r in enumerate(rollouts):
            seq = list(r.prompt_ids) + list(r.generated_ids)
            tokens[i, :len(seq)] = seq
        logp = np.asarray(self._fwd(self.params, tokens))   # [B, S, V]
        out = []
        for i, r in enumerate(rollouts):
            pl, g = len(r.prompt_ids), len(r.generated_ids)
            # source position pl-1+j predicts generated token j
            rows = logp[i, pl - 1:pl - 1 + g]               # [g, V]
            tok = rows[np.arange(g), np.asarray(r.generated_ids, np.int64)] \
                if g else np.zeros((0,), np.float32)
            out.append((tok, rows))
        return out


class RewardModelScorer(Scorer):
    """Sequence-level likelihood reward: the mean log-prob the scoring
    model assigns to the sampled continuation. ``model`` is a
    ``ModelPrograms`` (params shared with a resident engine) or a bundle
    with explicit ``params``."""

    def __init__(self, model, params=None):
        self._fwd = _ModelForward(model, params)

    def score(self, rollouts):
        return [Score(reward=float(tok.mean()) if len(tok) else 0.0)
                for tok, _ in self._fwd.token_logprobs(rollouts)]


class TeacherScorer(Scorer):
    """Distillation scoring: full-vocab teacher log-probs per
    continuation position (the ``distill_kl`` objective's data), plus
    the teacher's mean token log-prob as the scalar reward."""

    provides_teacher_logprobs = True

    def __init__(self, model, params=None):
        self._fwd = _ModelForward(model, params)

    def score(self, rollouts):
        return [Score(reward=float(tok.mean()) if len(tok) else 0.0,
                      teacher_logprobs=rows)
                for tok, rows in self._fwd.token_logprobs(rollouts)]
