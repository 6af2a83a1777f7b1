"""API-parity extras found in the r4 sweep against pyzoo: Ranker metrics,
util.nest, keras datasets loaders."""

import numpy as np
import pytest

from analytics_zoo_tpu.utils import nest


class TestNest:
    def test_flatten_sorted_dicts(self):
        s = {"b": [1, 2], "a": (3, {"z": 4, "y": 5})}
        assert nest.flatten(s) == [3, 5, 4, 1, 2]
        assert nest.flatten(7) == [7]

    def test_pack_roundtrip(self):
        s = {"b": [1, 2], "a": (3, {"z": 4, "y": 5})}
        flat = nest.flatten(s)
        rebuilt = nest.pack_sequence_as(s, [x * 10 for x in flat])
        assert rebuilt == {"b": [10, 20], "a": (30, {"z": 40, "y": 50})}
        assert isinstance(rebuilt["a"], tuple)

    def test_pack_mismatch_raises(self):
        with pytest.raises(ValueError, match="leaves"):
            nest.pack_sequence_as([1, 2], [1])
        with pytest.raises(ValueError, match="scalar"):
            nest.pack_sequence_as(1, [1, 2])


class TestRanker:
    def _model(self):
        from analytics_zoo_tpu.models.common import Ranker

        class M(Ranker):
            def predict(self, feats, batch_size=None):
                return np.asarray(feats)[:, :1]

        return M()

    def test_perfect_ranking(self):
        m = self._model()
        # scores equal labels: perfect ranking
        groups = [(np.array([[3.0], [2.0], [1.0], [0.0]]),
                   np.array([1.0, 1.0, 0.0, 0.0]))]
        assert m.evaluate_map(groups) == 1.0
        assert m.evaluate_ndcg(groups, k=4) == 1.0

    def test_known_map_value(self):
        m = self._model()
        # ranked relevance after sorting by score: [1, 0, 1, 0]
        groups = [(np.array([[4.0], [3.0], [2.0], [1.0]]),
                   np.array([1.0, 0.0, 1.0, 0.0]))]
        expect = (1 / 1 + 2 / 3) / 2
        assert abs(m.evaluate_map(groups) - expect) < 1e-9

    def test_ndcg_cutoff_and_no_positives(self):
        m = self._model()
        groups = [(np.array([[2.0], [1.0]]), np.array([0.0, 1.0])),
                  (np.array([[1.0]]), np.array([0.0]))]
        # group 1: relevant item ranked 2nd -> dcg 1/log2(3), idcg 1
        expect_g1 = (1 / np.log2(3)) / 1.0
        got = m.evaluate_ndcg(groups, k=2)
        assert abs(got - (expect_g1 + 0.0) / 2) < 1e-9
        # k=1 cuts the relevant item out entirely
        assert m.evaluate_ndcg([groups[0]], k=1) == 0.0

    def test_knrm_exposes_ranker(self, tmp_path):
        from analytics_zoo_tpu.models.textmatching import KNRM

        l1, l2, vocab = 4, 6, 30
        knrm = KNRM(l1, l2, vocab, embed_size=8, kernel_num=3)
        rng = np.random.default_rng(0)
        groups = [(rng.integers(1, vocab, (5, l1 + l2)).astype(np.float32),
                   (rng.random(5) > 0.5).astype(np.float32))
                  for _ in range(3)]
        ndcg = knrm.evaluate_ndcg(groups, k=3)
        mapv = knrm.evaluate_map(groups)
        assert 0.0 <= ndcg <= 1.0 and 0.0 <= mapv <= 1.0

    def test_textset_relation_lists_path(self):
        """End-to-end through TextSet.from_relation_lists — the reference
        call pattern (ranker.py consumes listwise TextSets)."""
        from analytics_zoo_tpu.feature.common import Relation
        from analytics_zoo_tpu.feature.text.text_set import (LocalTextSet,
                                                             TextSet)
        from analytics_zoo_tpu.feature.text.text_feature import TextFeature

        def corpus(prefix, n, length):
            feats = []
            for i in range(n):
                tf_ = TextFeature(text=f"{prefix} {i}", uri=f"{prefix}{i}")
                tf_[TextFeature.indexed_tokens] = np.full(length, i + 1,
                                                          np.float32)
                feats.append(tf_)
            return LocalTextSet(feats)

        c1 = corpus("q", 2, 3)
        c2 = corpus("d", 4, 5)
        rels = [Relation("q0", "d0", 1), Relation("q0", "d1", 0),
                Relation("q1", "d2", 0), Relation("q1", "d3", 1)]
        ts = TextSet.from_relation_lists(rels, c1, c2)
        m = self._model()
        assert 0.0 <= m.evaluate_map(ts) <= 1.0
        assert 0.0 <= m.evaluate_ndcg(ts, k=2) <= 1.0


class TestDatasets:
    def test_mnist_shapes(self):
        from analytics_zoo_tpu.pipeline.api.keras.datasets import mnist

        (xtr, ytr), (xte, yte) = mnist.load_data()
        assert xtr.shape[1:] == (28, 28, 1) and xtr.dtype == np.uint8
        assert len(xtr) == len(ytr) and len(xte) == len(yte)
        assert set(np.unique(ytr)) <= set(range(10))

    def test_mnist_parses_real_idx_files(self, tmp_path):
        import gzip
        import struct

        from analytics_zoo_tpu.pipeline.api.keras.datasets import mnist

        rng = np.random.default_rng(0)
        imgs = rng.integers(0, 255, (7, 28, 28), dtype=np.uint8)
        labels = rng.integers(0, 10, 7).astype(np.uint8)
        for name, magic, payload in (
                (mnist.TRAIN_IMAGES, 2051, imgs), (mnist.TEST_IMAGES, 2051,
                                                   imgs),
                (mnist.TRAIN_LABELS, 2049, labels),
                (mnist.TEST_LABELS, 2049, labels)):
            with gzip.open(tmp_path / name, "wb") as f:
                if magic == 2051:
                    f.write(struct.pack(">IIII", magic, 7, 28, 28))
                    f.write(payload.tobytes())
                else:
                    f.write(struct.pack(">II", magic, 7))
                    f.write(payload.tobytes())
        (xtr, ytr), _ = mnist.load_data(str(tmp_path))
        np.testing.assert_array_equal(xtr[..., 0], imgs)
        np.testing.assert_array_equal(ytr, labels)

    def test_imdb_nb_words_and_oov(self):
        from analytics_zoo_tpu.pipeline.api.keras.datasets import imdb

        (xtr, ytr), _ = imdb.load_data(nb_words=50, oov_char=2)
        flat = [w for seq in xtr for w in seq]
        assert max(flat) < 50
        (xtr2, _), _ = imdb.load_data(nb_words=50, oov_char=None)
        assert all(w < 50 for seq in xtr2 for w in seq)
        assert set(np.unique(ytr)) <= {0, 1}
        assert len(imdb.get_word_index()) > 100

    def test_boston_split(self):
        from analytics_zoo_tpu.pipeline.api.keras.datasets import \
            boston_housing

        (xtr, ytr), (xte, yte) = boston_housing.load_data(test_split=0.25)
        assert xtr.shape[1] == 13
        assert abs(len(xte) / (len(xtr) + len(xte)) - 0.25) < 0.01

    def test_reuters_classes(self):
        from analytics_zoo_tpu.pipeline.api.keras.datasets import reuters

        (xtr, ytr), (xte, yte) = reuters.load_data(nb_words=300)
        assert all(w < 300 for seq in xtr for w in seq)
        assert set(np.unique(ytr)) <= set(range(46))
        assert len(xte) > 0


class TestParityHoleLayers:
    """r5: the last four public-layer parity holes.

    References: SparseDense.scala, SelectTable.scala, Expand.scala /
    InternalExpand.scala (+ InternalExpandSpec), GetShape.scala.
    """

    def _build(self, layer, in_shape):
        import jax
        return layer.build(jax.random.PRNGKey(0), in_shape)

    def test_sparse_dense_forward_matches_dense(self):
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.pipeline.api.keras import layers as zl

        x = np.random.default_rng(0).standard_normal((3, 6)).astype(
            np.float32)
        sd = zl.SparseDense(4, activation="tanh")
        params = self._build(sd, (None, 6))
        dense = zl.Dense(4, activation="tanh")
        out = sd.call(params, jnp.asarray(x))
        ref = dense.call(params, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)
        assert sd.compute_output_shape((None, 6)) == (None, 4)

    def test_sparse_dense_blocks_input_gradient_by_default(self):
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.pipeline.api.keras import layers as zl

        sd = zl.SparseDense(4)
        params = self._build(sd, (None, 6))
        x = jnp.asarray(np.random.default_rng(1).standard_normal(
            (3, 6)).astype(np.float32))
        gx = jax.grad(lambda x_: sd.call(params, x_).sum())(x)
        np.testing.assert_array_equal(np.asarray(gx), 0.0)
        # ...but the kernel still trains
        gk = jax.grad(lambda p: sd.call(p, x).sum())(params)["kernel"]
        assert np.abs(np.asarray(gk)).sum() > 0

    def test_sparse_dense_backward_window(self):
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.pipeline.api.keras import layers as zl

        # backward_start is 1-based (Scala surface): window = dims 2..4
        sd = zl.SparseDense(4, backward_start=3, backward_length=2)
        params = self._build(sd, (None, 6))
        x = jnp.asarray(np.random.default_rng(2).standard_normal(
            (3, 6)).astype(np.float32))
        gx = np.asarray(jax.grad(
            lambda x_: sd.call(params, x_).sum())(x))
        assert np.abs(gx[:, 2:4]).sum() > 0
        np.testing.assert_array_equal(gx[:, :2], 0.0)
        np.testing.assert_array_equal(gx[:, 4:], 0.0)
        # windowed grad equals the plain-Dense grad on the window
        full = np.asarray(jax.grad(lambda x_: jnp.matmul(
            x_, params["kernel"]).sum() + params["bias"].sum())(x))
        np.testing.assert_allclose(gx[:, 2:4], full[:, 2:4],
                                   rtol=1e-6, atol=1e-6)

    def test_select_table(self):
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.pipeline.api.keras import layers as zl

        a = jnp.asarray(np.arange(6, dtype=np.float32).reshape(2, 3))
        b = jnp.asarray(np.ones((2, 5), np.float32))
        st = zl.SelectTable(1)
        out = st.call(None, [a, b])
        np.testing.assert_array_equal(np.asarray(out), np.asarray(b))
        # gradient routes only to the selected table entry
        ga, gb = jax.grad(lambda xs: st.call(None, xs).sum())([a, b])
        np.testing.assert_array_equal(np.asarray(ga), 0.0)
        np.testing.assert_array_equal(np.asarray(gb), 1.0)
        assert st.compute_output_shape([(None, 3), (None, 5)]) == (None, 5)

    def test_expand_matches_internal_expand_spec(self):
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.pipeline.api.keras import layers as zl

        # InternalExpandSpec: (5,4,1) -> (5,4,3); every slice == input
        x = np.random.default_rng(3).random((5, 4, 1)).astype(np.float32)
        for tgt in ((5, 4, 3), (-1, 4, 3)):
            layer = zl.Expand(tgt)
            out = np.asarray(layer.call(None, jnp.asarray(x)))
            assert out.shape == (5, 4, 3)
            for i in range(3):
                np.testing.assert_allclose(out[:, :, i:i + 1], x)
        # backward: sum over the expanded dim (broadcast transpose)
        layer = zl.Expand((5, 4, 3))
        g = np.random.default_rng(4).random((5, 4, 3)).astype(np.float32)
        gx = jax.grad(lambda x_: (layer.call(None, x_) *
                                  jnp.asarray(g)).sum())(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(gx),
                                   g.sum(axis=2, keepdims=True), rtol=1e-6)

    def test_expand_rejects_non_singleton(self):
        import jax.numpy as jnp
        from analytics_zoo_tpu.pipeline.api.keras import layers as zl

        with pytest.raises(ValueError, match="singleton"):
            zl.Expand((5, 4, 3)).call(None, jnp.zeros((5, 2, 1)))
        with pytest.raises(ValueError, match="every dim"):
            zl.Expand((4, 3)).call(None, jnp.zeros((5, 4, 1)))

    def test_get_shape(self):
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.pipeline.api.keras import layers as zl

        gs = zl.GetShape()
        x = jnp.zeros((2, 7, 3))
        np.testing.assert_array_equal(np.asarray(gs.call(None, x)),
                                      [2.0, 7.0, 3.0])
        gx = jax.grad(lambda x_: gs.call(None, x_).sum())(x)
        np.testing.assert_array_equal(np.asarray(gx), 0.0)
        assert gs.compute_output_shape((None, 7, 3)) == (3,)
