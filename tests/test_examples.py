"""Examples tier as smoke tests (SURVEY §4: the reference's examples are its
de-facto integration suite; runner analogue: run-example-tests.sh).

Two fast representatives always run; the full six run via
``ZOO_RUN_ALL_EXAMPLES=1 pytest tests/test_examples.py`` or
``python examples/run_examples.py``.
"""

import os
import subprocess
import sys

import pytest

EXAMPLES_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")

FAST = ["recommendation_wide_and_deep.py", "anomaly_detection.py"]
ALL = FAST + ["recommendation_ncf.py", "text_classification.py",
              "object_detection_ssd.py", "tfpark_bert_finetune.py",
              "ray_parameter_server.py", "streaming_inference.py",
              "automl_forecast.py", "seq2seq_copy.py",
              "image_finetune.py", "text_matching_knrm.py",
              "ray_reinforce.py", "variational_autoencoder.py",
              "fraud_detection.py", "image_augmentation.py",
              "image_augmentation_3d.py",
              "image_similarity.py",
              "model_inference_pipeline.py"]


def _run(name):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)      # examples are single-host scripts
    proc = subprocess.run([sys.executable, name, "--platform", "cpu"],
                          cwd=EXAMPLES_DIR, capture_output=True, text=True,
                          timeout=900, env=env)
    assert proc.returncode == 0, \
        f"{name} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"


@pytest.mark.parametrize("name", FAST)
def test_fast_examples(name):
    _run(name)


@pytest.mark.skipif(not os.environ.get("ZOO_RUN_ALL_EXAMPLES"),
                    reason="set ZOO_RUN_ALL_EXAMPLES=1 for the full tier")
@pytest.mark.parametrize("name", [n for n in ALL if n not in FAST])
def test_all_examples(name):
    _run(name)


# -- real reference fixtures -----------------------
# Each wired example asserts its analysis metric ON REAL DATA inside its
# real_* section (NCF: HR@10/NDCG@10 lift over random on genuine
# MovieLens ratings; Wide&Deep: accuracy over the majority class on the
# real categorical columns; text: post-level majority vote through the
# real TextSet pipeline + real GloVe; image: separability of the real
# cat_dog JPEGs through the decode pipeline). ZOO_ONLY_REAL runs just
# that leg.

REAL_FIXTURES = os.environ.get(
    "ZOO_REF_RESOURCES", "/root/reference/pyzoo/test/zoo/resources")
REAL_EXAMPLES = ["text_classification.py", "image_finetune.py",
                 "image_similarity.py", "object_detection_ssd.py",
                 "tfpark_bert_finetune.py"]
REAL_EXAMPLES_SLOW = ["recommendation_ncf.py",
                      "recommendation_wide_and_deep.py"]


def _run_real(name):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["ZOO_ONLY_REAL"] = "1"
    proc = subprocess.run([sys.executable, name, "--platform", "cpu"],
                          cwd=EXAMPLES_DIR, capture_output=True, text=True,
                          timeout=900, env=env)
    assert proc.returncode == 0, \
        f"{name} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    # a skipped real section also prints "... (real leg only)", so the
    # gate is the positive metric marker each real section emits
    assert "REAL " in proc.stdout, proc.stdout[-500:]


@pytest.mark.skipif(not os.path.isdir(REAL_FIXTURES),
                    reason="reference fixtures not present")
@pytest.mark.parametrize("name", REAL_EXAMPLES)
def test_real_fixture_examples(name):
    _run_real(name)


@pytest.mark.slow
@pytest.mark.skipif(not os.path.isdir(REAL_FIXTURES),
                    reason="reference fixtures not present")
@pytest.mark.parametrize("name", REAL_EXAMPLES_SLOW)
def test_real_fixture_examples_slow(name):
    _run_real(name)
