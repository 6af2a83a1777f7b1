"""Image-classifier transfer learning: freeze the trunk, retrain the head.

Reference config: the TFPark KerasModel ResNet-50 fine-tune
(dogs-vs-cats), the ``apps/dogs-vs-cats`` notebook — load a backbone,
freeze everything below the head, fit a 2-class classifier. Here a small
zoo backbone on synthetic two-texture images (no download; the reference
downloads its pretrained snapshot instead), using the GraphNet-parity
surgery: ``new_graph`` to re-root on the penultimate layer,
``freeze_up_to`` so only the new head trains.
"""

import os

import numpy as np

from common import cat_dog_real, example_args

from analytics_zoo_tpu.models.image.imageclassification import \
    ImageClassifier
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.models import Model
from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

SIZE = 32


def make_dataset(n, rng):
    """Class 0: vertical stripes; class 1: horizontal stripes (+noise)."""
    y = rng.integers(0, 2, n).astype(np.int32)
    x = rng.normal(0, 0.3, (n, 3, SIZE, SIZE)).astype(np.float32)
    stripes = (np.arange(SIZE) // 4 % 2).astype(np.float32) * 2 - 1
    x[y == 0] += stripes[None, None, None, :]       # vertical
    x[y == 1] += stripes[None, None, :, None]       # horizontal
    return x, y


def main():
    args = example_args("image transfer learning / freeze + new head",
                        epochs=6, samples=512, batch_size=64)
    if os.environ.get("ZOO_ONLY_REAL"):
        real_cat_dog_section(args)
        print("image fine-tune example OK (real leg only)")
        return
    rng = np.random.default_rng(args.seed)
    x, y = make_dataset(args.samples, rng)

    base = ImageClassifier(class_num=10, model_name="lenet",
                           input_shape=(3, SIZE, SIZE))
    graph_model = base.model
    # "pretrained" backbone: the reference downloads
    # analytics-zoo_resnet-50_imagenet; offline we pretrain briefly on the
    # source task so trunk features are meaningful
    graph_model.compile(optimizer=Adam(lr=2e-3),
                        loss="sparse_categorical_crossentropy")
    graph_model.fit(x, y, batch_size=args.batch_size,
                    nb_epoch=args.epochs)

    # surgery: re-root on the penultimate layer, bolt on a fresh 2-class
    # head, freeze the trunk (GraphNet.newGraph/freezeUpTo parity)
    names = [l.name for l in graph_model.graph_function().layers]
    trunk_out = names[-2]
    sub = graph_model.new_graph([trunk_out])
    head = Dense(2, activation="softmax", name="finetune_head")(
        sub.outputs[0])
    tl = Model(sub.inputs, head)
    trunk_params = dict(graph_model.get_params())
    tl.compile(optimizer=Adam(lr=5e-3),
               loss="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    trainer = tl._ensure_trainer()
    trainer.ensure_initialized()
    merged = {k: (trunk_params[k] if k in trunk_params else v)
              for k, v in trainer.params.items()}
    trainer.set_params(merged, trainer.net_state)
    tl.freeze_up_to(trunk_out)
    print(f"frozen {len(tl.frozen_layers())} trunk layers; "
          f"training head only")
    tl.fit(x, y, batch_size=args.batch_size, nb_epoch=args.epochs)
    res = tl.evaluate(x, y, batch_size=args.batch_size)
    print(f"frozen-trunk head: {res}")

    # unfreeze and fine-tune everything briefly
    tl.unfreeze()
    tl.fit(x, y, batch_size=args.batch_size, nb_epoch=2)
    res = tl.evaluate(x, y, batch_size=args.batch_size)
    print(f"after full fine-tune: {res}")
    assert res["accuracy"] > 0.8, res

    real_cat_dog_section(args)
    print("image fine-tune example OK")


def real_cat_dog_section(args):
    """REAL data: the reference's dogs-vs-cats JPEGs (the actual
    fixture behind the ``apps/dogs-vs-cats`` notebook) streamed through
    the parallel decode pipeline into a fresh classifier fine-tune."""
    root = cat_dog_real()
    if root is None:
        print("reference fixtures absent; skipping real cat_dog leg")
        return
    from analytics_zoo_tpu.feature.image import ImagePipelineFeatureSet

    fs = ImagePipelineFeatureSet.read_folder(
        root, height=SIZE, width=SIZE, num_workers=2,
        one_based_label=False, data_format="th",
        mean=(104.0, 117.0, 123.0), std=(58.0, 57.0, 57.0))
    print(f"real cat_dog: {fs.size()} JPEGs, classes {fs.label_map}")

    clf = ImageClassifier(class_num=2, model_name="lenet",
                          input_shape=(3, SIZE, SIZE))
    clf.model.compile(optimizer=Adam(lr=3e-3),
                      loss="sparse_categorical_crossentropy",
                      metrics=["accuracy"])
    clf.model.fit(fs, batch_size=4, nb_epoch=8 * args.epochs)
    # evaluate on the decoded images directly (train-set memorization:
    # 12 real photos must be fully separable for a working pipeline)
    batches = list(fs.batches(fs.size(), shuffle=False,
                              drop_remainder=False))
    xs = np.concatenate([b.inputs[0] for b in batches])
    ys = np.concatenate([b.targets for b in batches]).astype(np.int32)
    res = clf.model.evaluate(xs, ys, batch_size=16)
    print(f"REAL cat_dog train-set evaluation: {res}")
    assert res["accuracy"] >= 0.9, res


if __name__ == "__main__":
    main()
