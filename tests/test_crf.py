"""CRF: forward-algorithm + Viterbi vs brute-force enumeration, and the
BiLSTM-CRF text models end-to-end (reference head:
pyzoo/zoo/tfpark/text/keras/ner.py:49 NERCRF)."""

import itertools

import numpy as np
import pytest

from analytics_zoo_tpu.ops.crf import (crf_decode, crf_log_likelihood,
                                       crf_log_normalizer,
                                       crf_sequence_score)


def _brute_force(unary, trans, mask=None):
    """All-paths enumeration: (logZ, best_path, best_score) per sequence."""
    b, l, e = unary.shape
    logzs, bests, best_scores = [], [], []
    for i in range(b):
        n = int(mask[i].sum()) if mask is not None else l
        scores = {}
        for path in itertools.product(range(e), repeat=n):
            s = unary[i, 0, path[0]]
            for t in range(1, n):
                s += trans[path[t - 1], path[t]] + unary[i, t, path[t]]
            scores[path] = s
        vals = np.array(list(scores.values()))
        logzs.append(np.log(np.exp(vals - vals.max()).sum()) + vals.max())
        best = max(scores, key=scores.get)
        bests.append(list(best) + [0] * (l - n))
        best_scores.append(scores[best])
    return np.array(logzs), np.array(bests), np.array(best_scores)


def test_crf_matches_brute_force(rng):
    b, l, e = 3, 5, 3
    unary = rng.standard_normal((b, l, e)).astype(np.float32)
    trans = rng.standard_normal((e, e)).astype(np.float32)

    logz_bf, best_bf, best_score_bf = _brute_force(unary, trans)
    logz = np.asarray(crf_log_normalizer(unary, trans))
    np.testing.assert_allclose(logz, logz_bf, rtol=1e-5)

    tags, score = crf_decode(unary, trans)
    np.testing.assert_array_equal(np.asarray(tags), best_bf)
    np.testing.assert_allclose(np.asarray(score), best_score_bf, rtol=1e-5)

    # log-likelihood of the best path = best_score - logZ
    ll = np.asarray(crf_log_likelihood(unary, np.asarray(tags), trans))
    np.testing.assert_allclose(ll, best_score_bf - logz_bf, rtol=1e-5,
                               atol=1e-5)


def test_crf_masked_matches_brute_force(rng):
    b, l, e = 2, 6, 3
    unary = rng.standard_normal((b, l, e)).astype(np.float32)
    trans = rng.standard_normal((e, e)).astype(np.float32)
    mask = np.zeros((b, l), np.float32)
    mask[0, :4] = 1
    mask[1, :6] = 1

    logz_bf, best_bf, _ = _brute_force(unary, trans, mask)
    logz = np.asarray(crf_log_normalizer(unary, trans, mask))
    np.testing.assert_allclose(logz, logz_bf, rtol=1e-5)

    tags, _ = crf_decode(unary, trans, mask)
    tags = np.asarray(tags) * mask.astype(np.int32)
    np.testing.assert_array_equal(tags, np.array(best_bf) *
                                  mask.astype(np.int64))

    # a valid path's likelihood is invariant to what the pad tail says
    t0 = np.array(best_bf)
    t1 = t0.copy()
    t1[0, 4:] = 2
    ll0 = np.asarray(crf_log_likelihood(unary, t0, trans, mask))
    ll1 = np.asarray(crf_log_likelihood(unary, t1, trans, mask))
    np.testing.assert_allclose(ll0, ll1, rtol=1e-6)


def test_crf_loss_gradients_flow(rng):
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.crf import crf_log_likelihood as ll

    b, l, e = 2, 4, 3
    unary = jnp.asarray(rng.standard_normal((b, l, e)), jnp.float32)
    trans = jnp.asarray(rng.standard_normal((e, e)), jnp.float32)
    tags = jnp.asarray(rng.integers(0, e, (b, l)), jnp.int32)

    g_u, g_t = jax.grad(lambda u, t: -ll(u, tags, t).mean(),
                        argnums=(0, 1))(unary, trans)
    assert np.isfinite(np.asarray(g_u)).all()
    assert np.isfinite(np.asarray(g_t)).all()
    assert float(jnp.abs(g_t).sum()) > 0


def test_ner_crf_trains_and_decodes(rng):
    from analytics_zoo_tpu.tfpark.text.keras import NER

    b, l, w, e = 8, 6, 4, 4
    model = NER(num_entities=e, word_vocab_size=30, char_vocab_size=10,
                word_length=w, word_emb_dim=8, char_emb_dim=4,
                tagger_lstm_dim=8, seq_len=l)
    words = rng.integers(0, 30, (b, l)).astype(np.int32)
    chars = rng.integers(0, 10, (b, l, w)).astype(np.int32)
    tags = rng.integers(0, e, (b, l)).astype(np.int32)
    model.fit([words, chars], tags, batch_size=4, epochs=2)
    preds = model.predict([words, chars], batch_size=4)
    assert preds.shape == (b, l, e)
    assert np.allclose(preds.sum(-1), 1.0)     # one-hot decodes
    int_tags = model.predict_tags([words, chars], batch_size=4)
    assert int_tags.shape == (b, l)
    assert int_tags.max() < e


def test_ner_crf_pad_mode(rng):
    from analytics_zoo_tpu.tfpark.text.keras import NER

    b, l, w, e = 4, 6, 3, 3
    model = NER(num_entities=e, word_vocab_size=20, char_vocab_size=8,
                word_length=w, word_emb_dim=8, char_emb_dim=4,
                tagger_lstm_dim=8, crf_mode="pad", seq_len=l)
    words = rng.integers(0, 20, (b, l)).astype(np.int32)
    chars = rng.integers(0, 8, (b, l, w)).astype(np.int32)
    lens = np.array([3, 6, 4, 5], np.int32)
    tags = rng.integers(0, e, (b, l)).astype(np.int32)
    model.fit([words, chars, lens], tags, batch_size=4, epochs=1)
    int_tags = model.predict_tags([words, chars, lens], batch_size=4)
    assert int_tags.shape == (b, l)
    assert (int_tags[0, 3:] == 0).all()        # pad tail masked to 0


def test_sequence_tagger_crf(rng):
    from analytics_zoo_tpu.tfpark.text.keras import SequenceTagger

    b, l, p, c = 8, 5, 4, 3
    model = SequenceTagger(num_pos_labels=p, num_chunk_labels=c,
                           word_vocab_size=25, feature_size=8,
                           classifier="crf", seq_len=l)
    words = rng.integers(0, 25, (b, l)).astype(np.int32)
    pos = rng.integers(0, p, (b, l)).astype(np.int32)
    chunk = rng.integers(0, c, (b, l)).astype(np.int32)
    model.fit([words], [pos, chunk], batch_size=4, epochs=2)
    preds = model.predict([words], batch_size=4)
    assert preds[0].shape == (b, l, p)
    assert preds[1].shape == (b, l, c)
    assert np.allclose(preds[0].sum(-1), 1.0)
