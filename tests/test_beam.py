"""Beam-search decode contracts: beams=1 == greedy, beam-K never scores
below greedy under teacher-forced log-prob, EOS padding convention.
(The reference decodes greedy-only, utils/metrics.py:74-149.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_apply, gpt2_init
from quintnet_tpu.models.gpt2_generate import gpt2_beam_search, gpt2_generate

CFG = GPT2Config.tiny()


@pytest.fixture(scope="module")
def setup():
    params = gpt2_init(jax.random.key(0), CFG)
    ids = np.asarray(
        np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 6)),
        np.int32)
    return params, ids


def _seq_logprob(params, full, t0):
    """Teacher-forced log-prob of the generated suffix."""
    logits = gpt2_apply(params, jnp.asarray(full), CFG)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tgt = full[:, 1:]
    tok_lp = np.take_along_axis(np.asarray(logp[:, :-1]),
                                tgt[:, :, None], axis=2)[:, :, 0]
    return tok_lp[:, t0 - 1:].sum(axis=1)


def test_beam1_equals_greedy(setup):
    params, ids = setup
    greedy = gpt2_generate(params, ids, CFG, max_new_tokens=6)
    beam = gpt2_beam_search(params, ids, CFG, beams=1, max_new_tokens=6)
    np.testing.assert_array_equal(greedy, beam)


def test_beam_scores_at_least_greedy(setup):
    params, ids = setup
    greedy = gpt2_generate(params, ids, CFG, max_new_tokens=6)
    beam = gpt2_beam_search(params, ids, CFG, beams=4, max_new_tokens=6)
    lp_g = _seq_logprob(params, greedy, ids.shape[1])
    lp_b = _seq_logprob(params, beam, ids.shape[1])
    assert (lp_b >= lp_g - 1e-4).all(), (lp_b, lp_g)


def test_beam_eos_pads_tail(setup):
    params, ids = setup
    eos = 7
    out = gpt2_beam_search(params, ids, CFG, beams=3, max_new_tokens=8,
                           eos_token_id=eos)
    assert out.shape == (2, 14)
    new = out[:, 6:]
    for row in new:
        hits = np.where(row == eos)[0]
        if hits.size:
            assert (row[hits[0]:] == eos).all()


def test_beam_shape_without_eos(setup):
    params, ids = setup
    out = gpt2_beam_search(params, ids, CFG, beams=2, max_new_tokens=1)
    assert out.shape == (2, 7)


def test_evaluate_generation_with_beams(setup):
    from quintnet_tpu.data import ByteTokenizer
    from quintnet_tpu.train.metrics import evaluate_generation

    params, _ = setup
    tok = ByteTokenizer()
    prompts = [([1, 2, 3, 4], "some reference"),
               ([5, 6, 7, 8], "other reference")]
    scores = evaluate_generation(params, CFG, prompts, tok,
                                 max_new_tokens=4, batch_size=2,
                                 beams=3)
    assert set(scores) == {"rouge1", "rouge2", "rougeL", "bleu"}
