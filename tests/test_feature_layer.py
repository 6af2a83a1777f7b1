"""Feature-layer tests: Preprocessing chains, ImageSet + ops, Image3D,
TextSet pipeline, Relations (SURVEY.md §2.4)."""

import os

import numpy as np
import pytest

from analytics_zoo_tpu.feature import (ArrayToTensor, ChainedPreprocessing,
                                       FeatureLabelPreprocessing, Relation,
                                       Relations, SampleToMiniBatch, Sample,
                                       ScalarToTensor, SeqToTensor)
from analytics_zoo_tpu.feature.image import (ImageBrightness, ImageCenterCrop,
                                             ImageChannelNormalize,
                                             ImageChannelOrder, ImageExpand,
                                             ImageFeature, ImageHFlip,
                                             ImageMatToTensor, ImageResize,
                                             ImageSet, ImageSetToSample,
                                             PerImageNormalize)
from analytics_zoo_tpu.feature.image3d import (CenterCrop3D, Crop3D,
                                               Rotate3D)
from analytics_zoo_tpu.feature.text import (TextFeature, TextSet)


def test_preprocessing_chain_composes():
    chain = SeqToTensor([4]) >> ArrayToTensor([2, 2])
    out = chain.apply([1, 2, 3, 4])
    assert out.shape == (2, 2)
    chain2 = ChainedPreprocessing([ScalarToTensor(), ArrayToTensor()])
    assert chain2.apply(3.0).shape == ()


def test_feature_label_preprocessing_and_batching():
    flp = FeatureLabelPreprocessing(SeqToTensor([2]), ScalarToTensor())
    samples = [flp.apply(([i, i + 1], i % 2)) for i in range(5)]
    assert all(isinstance(s, Sample) for s in samples)
    batches = list(SampleToMiniBatch(2)(iter(samples)))
    assert len(batches) == 3
    assert batches[0].inputs[0].shape == (2, 2)
    assert batches[-1].inputs[0].shape == (1, 2)


def _img(h=32, w=48, c=3, seed=0):
    return np.random.default_rng(seed).uniform(
        0, 255, (h, w, c)).astype(np.float32)


def test_image_ops():
    feat = ImageFeature(_img())
    out = ImageResize(16, 20).apply(feat)
    assert out.get_image().shape == (16, 20, 3)
    out = ImageCenterCrop(8, 8).apply(out)
    assert out.get_image().shape == (8, 8, 3)
    img = out.get_image().copy()
    flipped = ImageHFlip().apply(out).get_image()
    np.testing.assert_allclose(flipped, img[:, ::-1])

    norm = ImageChannelNormalize(10, 20, 30, 2, 2, 2).apply(
        ImageFeature(np.ones((4, 4, 3), np.float32) * 50)).get_image()
    # mat is BGR: channel 0 normalized with mean_b=30
    np.testing.assert_allclose(norm[..., 0], (50 - 30) / 2)
    np.testing.assert_allclose(norm[..., 2], (50 - 10) / 2)

    per = PerImageNormalize(0, 1).apply(ImageFeature(_img())).get_image()
    assert 0.0 <= per.min() < 1e-6 and 1 - 1e-6 < per.max() <= 1.0

    exp = ImageExpand(min_expand_ratio=2.0, max_expand_ratio=2.0).apply(
        ImageFeature(_img(10, 10))).get_image()
    assert exp.shape == (20, 20, 3)

    rgb = ImageChannelOrder().apply(ImageFeature(_img())).get_image()
    np.testing.assert_allclose(rgb[..., 0], _img()[..., 2])


def test_image_mat_to_tensor_and_sample():
    feat = ImageFeature(_img(8, 8), label=3.0)
    feat = ImageMatToTensor(format="NCHW").apply(feat)
    assert feat["floats"].shape == (3, 8, 8)
    feat = ImageSetToSample().apply(feat)
    s = feat.get_sample()
    assert s.features[0].shape == (3, 8, 8)
    assert float(s.labels[0]) == 3.0


def test_image_set_read_with_label(tmp_path):
    import cv2

    for cls in ("cat", "dog"):
        os.makedirs(tmp_path / cls)
        for i in range(3):
            cv2.imwrite(str(tmp_path / cls / f"{i}.jpg"),
                        np.random.default_rng(i).integers(
                            0, 255, (16, 16, 3)).astype(np.uint8))
    iset = ImageSet.read(str(tmp_path), with_label=True)
    assert len(iset) == 6
    labels = sorted(set(float(l) for l in iset.get_label()))
    assert labels == [1.0, 2.0]

    iset.transform(ImageResize(8, 8))
    iset.transform(ImageMatToTensor(format="NHWC"))
    iset.transform(ImageSetToSample())
    fs = iset.to_feature_set()
    assert fs.size() == 6
    batch = next(fs.batches(6, drop_remainder=False))
    assert batch.inputs[0].shape == (6, 8, 8, 3)


def test_image3d_ops():
    vol = np.random.default_rng(0).standard_normal((10, 12, 14)) \
        .astype(np.float32)
    feat = ImageFeature(vol)
    out = Crop3D([1, 2, 3], [4, 5, 6]).apply(feat).get_image()
    np.testing.assert_allclose(out, vol[1:5, 2:7, 3:9])
    out = CenterCrop3D(4, 4, 4).apply(ImageFeature(vol)).get_image()
    assert out.shape == (4, 4, 4)
    rot = Rotate3D([np.pi, 0, 0]).apply(ImageFeature(vol)).get_image()
    assert rot.shape == vol.shape


def test_textset_pipeline(tmp_path):
    texts = ["Hello World hello", "goodbye world!", "the quick brown fox",
             "the lazy dog sleeps"]
    labels = [0, 0, 1, 1]
    ts = TextSet.array([TextFeature(t, l, uri=f"doc{i}")
                        for i, (t, l) in enumerate(zip(texts, labels))])
    ts.tokenize().normalize().word2idx().shape_sequence(5).generate_sample()
    idx = ts.get_word_index()
    assert idx["world"] >= 1 and idx["the"] >= 1
    samples = ts.get_samples()
    assert all(s.features[0].shape == (5,) for s in samples)
    fs = ts.to_feature_set()
    assert fs.size() == 4

    # word index round trip
    p = str(tmp_path / "vocab.txt")
    ts.save_word_index(p)
    ts2 = TextSet.array([TextFeature("hello world")]).load_word_index(p)
    assert ts2.get_word_index() == idx

    # frequency options
    ts3 = TextSet.array([TextFeature(t) for t in texts]).tokenize() \
        .normalize()
    m = ts3.generate_word_index_map(min_freq=2)
    assert set(m) == {"world", "hello", "the"}


def test_relations_and_ranking_sets(tmp_path):
    corpus1 = TextSet.array([TextFeature("apple banana", uri="q1"),
                             TextFeature("cherry date", uri="q2")])
    corpus2 = TextSet.array([TextFeature("apple pie recipe", uri="d1"),
                             TextFeature("banana split recipe", uri="d2"),
                             TextFeature("random other words", uri="d3")])
    for c, n in ((corpus1, 3), (corpus2, 4)):
        c.tokenize().normalize().word2idx().shape_sequence(n)
    relations = [Relation("q1", "d1", 1), Relation("q1", "d3", 0),
                 Relation("q2", "d2", 1), Relation("q2", "d3", 0)]
    pairs_ts = TextSet.from_relation_pairs(relations, corpus1, corpus2)
    assert len(pairs_ts) == 2
    s = pairs_ts.get_samples()[0]
    assert s.features[0].shape == (2, 7)
    np.testing.assert_allclose(np.asarray(s.labels[0]), [[1.0], [0.0]])

    lists_ts = TextSet.from_relation_lists(relations, corpus1, corpus2)
    assert len(lists_ts) == 2
    s = lists_ts.get_samples()[0]
    assert s.features[0].shape == (2, 7)

    # csv read
    p = tmp_path / "rel.csv"
    p.write_text("id1,id2,label\nq1,d1,1\nq1,d3,0\n")
    rels = Relations.read(str(p))
    assert rels == [Relation("q1", "d1", 1), Relation("q1", "d3", 0)]


def test_sharded_file_feature_set_csv_and_striping(tmp_path):
    """Per-host striped file shards stream without materializing the
    dataset (SURVEY hard part (a))."""
    import pandas as pd
    from analytics_zoo_tpu.feature.feature_set import (FeatureSet,
                                                       ShardedFileFeatureSet)

    rng = np.random.default_rng(0)
    paths = []
    for i in range(4):
        df = pd.DataFrame({"a": rng.standard_normal(10),
                           "b": rng.standard_normal(10),
                           "label": rng.integers(0, 2, 10)})
        p = str(tmp_path / f"shard{i}.csv")
        df.to_csv(p, index=False)
        paths.append(p)

    fs = FeatureSet.files(paths, label_col="label")
    assert fs.size() == 40
    batches = list(fs.batches(8, drop_remainder=True))
    assert len(batches) == 5
    assert batches[0].inputs[0].shape == (8, 2)
    assert batches[0].targets is not None

    # striping: process 1 of 2 sees every other shard
    fs1 = ShardedFileFeatureSet(paths, label_col="label",
                                process_index=1, num_processes=2)
    assert fs1.size() == 20
    assert [p for p in fs1.paths] == [paths[1], paths[3]]


def test_sharded_file_feature_set_trains(tmp_path):
    from analytics_zoo_tpu.common.zoo_trigger import MaxEpoch
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam
    from analytics_zoo_tpu.feature.feature_set import DiskFeatureSet

    rng = np.random.default_rng(1)
    paths = []
    for i in range(3):
        x = rng.standard_normal((32, 4)).astype(np.float32)
        y = (x[:, :1] > 0).astype(np.float32)
        p = str(tmp_path / f"s{i}.npz")
        DiskFeatureSet.write_shard(p, x, y)
        paths.append(p)

    fs = FeatureSet.files(paths, num_slice=1)
    model = Sequential()
    model.add(Dense(8, activation="relu", input_shape=(4,)))
    model.add(Dense(1, activation="sigmoid"))
    model.compile(optimizer=Adam(lr=0.02), loss="binary_crossentropy")
    trainer = model._ensure_trainer()
    record = trainer.train(fs, batch_size=16, end_trigger=MaxEpoch(5))
    assert record.loss < 0.6


def test_file_io_scheme_registry(tmp_path):
    """Utils/File parity: scheme-dispatched IO with a registerable
    filesystem (the reference's HDFS-aware helpers)."""
    from analytics_zoo_tpu.utils import file_io

    p = str(tmp_path / "x.bin")
    file_io.write_bytes(p, b"abc")
    assert file_io.read_bytes("file://" + p) == b"abc"
    assert file_io.exists(p)
    assert file_io.glob(str(tmp_path / "*.bin")) == [p]

    class MemFS(file_io.FileSystem):
        store = {}

        def open(self, path, mode="rb"):
            import io
            if "w" in mode:
                buf = io.BytesIO()
                buf.close = lambda b=buf, p=path: MemFS.store.__setitem__(
                    p, b.getvalue())
                return buf
            return io.BytesIO(MemFS.store[path])

        def exists(self, path):
            return path in MemFS.store

    file_io.register_filesystem("mem", MemFS())
    file_io.write_bytes("mem://k", b"zzz")
    assert file_io.read_bytes("mem://k") == b"zzz"
    import pytest as _pytest
    with _pytest.raises(ValueError, match="no filesystem registered"):
        file_io.read_bytes("hdfs://nn/x")


def test_file_io_scheme_registry(tmp_path):
    """Utils/File parity: scheme-dispatched IO with a registerable
    filesystem (the reference's HDFS-aware helpers)."""
    from analytics_zoo_tpu.utils import file_io

    p = str(tmp_path / "x.bin")
    file_io.write_bytes(p, b"abc")
    assert file_io.read_bytes("file://" + p) == b"abc"
    assert file_io.exists(p)
    assert file_io.glob(str(tmp_path / "*.bin")) == [p]

    class MemFS(file_io.FileSystem):
        store = {}

        def open(self, path, mode="rb"):
            import io
            if "w" in mode:
                buf = io.BytesIO()
                buf.close = lambda b=buf, p=path: MemFS.store.__setitem__(
                    p, b.getvalue())
                return buf
            return io.BytesIO(MemFS.store[path])

        def exists(self, path):
            return path in MemFS.store

    file_io.register_filesystem("mem", MemFS())
    file_io.write_bytes("mem://k", b"zzz")
    assert file_io.read_bytes("mem://k") == b"zzz"
    import pytest as _pytest
    with _pytest.raises(ValueError, match="no filesystem registered"):
        file_io.read_bytes("hdfs://nn/x")


class TestImagePipeline:
    """r5 streaming decode pipeline (feature/image/pipeline.py) — the
    throughput-bearing input path for SURVEY §7 hard-part (c)."""

    @pytest.fixture(scope="class")
    def jpeg_dir(self, tmp_path_factory):
        cv2 = pytest.importorskip("cv2")
        root = tmp_path_factory.mktemp("imgs")
        rng = np.random.default_rng(0)
        for cls in ("cats", "dogs"):
            (root / cls).mkdir()
            for i in range(5):
                img = rng.integers(0, 255, (48 + 8 * i, 64, 3), np.uint8)
                cv2.imwrite(str(root / cls / f"{cls}{i}.jpg"), img)
        return str(root)

    def test_content_matches_eager_imageset(self, jpeg_dir):
        """Same files, same resize -> identical arrays as the eager
        ImageSet.read path (both BGR, both cv2.resize INTER_LINEAR)."""
        from analytics_zoo_tpu.feature.image import (ImagePipelineFeatureSet,
                                                     ImageSet)

        fs = ImagePipelineFeatureSet.read_folder(jpeg_dir, height=32,
                                                 width=32, num_workers=2)
        got = list(fs.batches(5, shuffle=False))
        eager = ImageSet.read(jpeg_dir, resize_h=32, resize_w=32,
                              with_label=True)
        want = np.stack([f.get_image() for f in eager.features])
        want_labels = np.asarray(eager.get_label(), np.float32)
        xs = np.concatenate([b.inputs[0] for b in got])
        ys = np.concatenate([b.targets for b in got])
        np.testing.assert_allclose(xs, want, atol=1e-4)
        np.testing.assert_array_equal(ys, want_labels)

    def test_stats_shuffle_and_remainder(self, jpeg_dir):
        from analytics_zoo_tpu.feature.image import ImagePipelineFeatureSet

        fs = ImagePipelineFeatureSet.read_folder(jpeg_dir, height=16,
                                                 width=16, num_workers=2)
        assert fs.size() == 10
        # drop_remainder: 10 -> 3 batches of 3
        n = sum(1 for _ in fs.batches(3, shuffle=True, seed=7))
        assert n == 3
        assert fs.stats.batches == 3 and fs.stats.images == 9
        assert fs.stats.elapsed_s > 0 and fs.stats.throughput() > 0
        # pad_remainder keeps every batch full
        shapes = [b.inputs[0].shape[0] for b in
                  fs.batches(4, drop_remainder=False, pad_remainder=True)]
        assert shapes == [4, 4, 4]
        # same seed -> same order
        a = np.concatenate([b.targets for b in
                            fs.batches(3, shuffle=True, seed=5)])
        b = np.concatenate([b.targets for b in
                            fs.batches(3, shuffle=True, seed=5)])
        np.testing.assert_array_equal(a, b)

    def test_augment_and_chw(self, jpeg_dir):
        from analytics_zoo_tpu.feature.image import ImagePipelineFeatureSet

        fs = ImagePipelineFeatureSet.read_folder(
            jpeg_dir, height=16, width=16, num_workers=1,
            augment=_double, data_format="th",
            mean=(1.0, 2.0, 3.0))
        b = next(iter(fs.batches(4)))
        assert b.inputs[0].shape == (4, 3, 16, 16)
        # augment ran before mean-subtract: values can exceed 255
        assert b.inputs[0].max() > 255.0

    def test_fit_through_pipeline(self, jpeg_dir):
        """End-to-end: Model.fit consumes the pipeline FeatureSet."""
        from analytics_zoo_tpu.feature.image import ImagePipelineFeatureSet
        from analytics_zoo_tpu.pipeline.api.keras.layers import (Dense,
                                                                 Flatten)
        from analytics_zoo_tpu.pipeline.api.keras.models import Sequential

        fs = ImagePipelineFeatureSet.read_folder(
            jpeg_dir, height=8, width=8, num_workers=2,
            one_based_label=False, std=(255.0, 255.0, 255.0))
        m = Sequential()
        m.add(Flatten(input_shape=(8, 8, 3)))
        m.add(Dense(2, activation="softmax"))
        m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
        m.fit(fs, batch_size=5, nb_epoch=2)
        p = m.predict(np.zeros((2, 8, 8, 3), np.float32), batch_size=2)
        assert p.shape == (2, 2)


def _double(img):
    return img * 2.0
