"""generate() held to the no-cache reference (the serving engine shares
generate's prefill/decode bodies — this file is what makes "refactor, don't
fork" enforceable).

The reference is computed here, on the same params, by full forward passes
(``generate.full_forward_logits``): greedy tokens must be its argmax at
every position (to a float32 rounding margin at near-ties), and sampled
tokens must be what the SAME rng chain draws from the reference's logits.
Tokens frozen under another jax drift on random weights; these do not.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.generate import (
    _make_pick,
    full_forward_logits,
    generate,
    greedy_agreement,
    pad_prompts,
)

_NEW = 11
_SAMPLING = dict(temperature=0.8, top_k=7, top_p=0.9)
# float32 on the CPU: cached decode and full forward differ by rounding only.
_TOL = 1e-4


def _setup(name: str):
    model = models.get_model(name, size="tiny", vocab_size=97, max_len=64)
    rng = np.random.default_rng(42)
    prompts = [list(map(int, rng.integers(1, 97, n))) for n in (5, 9, 3)]
    padded, lens = pad_prompts(prompts, pad_id=0)
    params = model.init(jax.random.PRNGKey(7), padded)["params"]
    return model, params, prompts, padded, lens


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_generate_greedy_matches_full_forward_reference(name):
    model, params, prompts, padded, lens = _setup(name)
    out = np.asarray(generate(
        model, params, padded, max_new_tokens=_NEW, prompt_lens=lens
    ))
    rec = greedy_agreement(
        model, params, prompts, [list(row[-_NEW:]) for row in out]
    )
    assert rec["tokens"] == len(prompts) * _NEW
    assert rec["worst_logit_gap"] <= _TOL, rec


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_generate_sampled_matches_reference_draws(name):
    model, params, prompts, padded, lens = _setup(name)
    out = np.asarray(generate(
        model, params, padded, max_new_tokens=_NEW, prompt_lens=lens,
        rng=jax.random.PRNGKey(13), **_SAMPLING,
    ))[:, -_NEW:]
    # Teacher-forced reference logits per row, then generate()'s own pick
    # (temperature -> top-k/top-p -> categorical) on generate()'s rng chain.
    buf = np.zeros((len(prompts), 32), np.int32)
    for b, p in enumerate(prompts):
        buf[b, : len(p) + _NEW] = p + list(out[b])
    logits = full_forward_logits(model, params, buf)
    pick = _make_pick(
        jnp.float32(_SAMPLING["temperature"]), jnp.int32(_SAMPLING["top_k"]),
        jnp.float32(_SAMPLING["top_p"]), sample=True, filtered=True,
    )
    rng = jax.random.PRNGKey(13)
    rows = np.arange(len(prompts))
    for i in range(_NEW):
        step_logits = logits[rows, np.asarray(lens) - 1 + i]
        want, rng = pick(step_logits, rng)
        np.testing.assert_array_equal(out[:, i], np.asarray(want), f"step {i}")
