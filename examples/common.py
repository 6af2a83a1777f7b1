"""Shared helpers for the runnable examples.

The reference ships 66 example mains (``pyzoo/zoo/examples/``) and 16
notebook apps (``apps/``) that download public datasets. These examples are
self-contained instead: each synthesizes a dataset with the same schema as
the reference example's (MovieLens ratings, Census rows, news20-style text,
NYC-taxi-style series), so every script runs offline on CPU in under a
minute and doubles as an integration smoke test (SURVEY §4: the examples
tier is the reference's de-facto integration suite).
"""

import argparse
import os
import sys

import numpy as np

# examples are runnable from a checkout without installing the package
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def example_args(description, **extra):
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--epochs", type=int, default=extra.get("epochs", 3))
    p.add_argument("--batch-size", type=int,
                   default=extra.get("batch_size", 128))
    p.add_argument("--samples", type=int, default=extra.get("samples", 2048))
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", choices=["cpu", "default"], default="cpu",
                   help="cpu (hermetic, default) or the environment's "
                        "default accelerator")
    if extra.get("extra_args") is not None:
        extra["extra_args"](p)
    args = p.parse_args()
    if args.platform == "cpu":
        import jax
        # the flag wins over whatever JAX_PLATFORMS says
        jax.config.update("jax_platforms", "cpu")
    return args


def movielens_like(n, n_users=200, n_items=100, seed=0):
    """(user, item) int pairs + 1-5 star labels with learnable structure."""
    rng = np.random.default_rng(seed)
    users = rng.integers(1, n_users + 1, n)
    items = rng.integers(1, n_items + 1, n)
    stars = ((users * 7 + items * 13) % 5).astype(np.int32)  # deterministic
    x = np.stack([users, items], axis=1).astype(np.float32)
    return x, stars, n_users, n_items


def census_like(n, seed=0):
    """Census-income-style rows for Wide&Deep (reference:
    pyzoo/zoo/examples/recommendation/wide_n_deep.py feature columns)."""
    rng = np.random.default_rng(seed)
    edu = rng.integers(0, 16, n)          # education (wide base + embed)
    occ = rng.integers(0, 1000, n)        # occupation hash bucket
    gender = rng.integers(0, 2, n)        # indicator
    age = rng.uniform(17, 90, n)          # continuous
    hours = rng.uniform(1, 99, n)         # continuous
    label = ((edu > 9) & (hours > 40) | (occ % 7 == 0)).astype(np.int32)
    return {"education": edu, "occupation": occ, "gender": gender,
            "age": age.astype(np.float32),
            "hours_per_week": hours.astype(np.float32), "label": label}


def news_like(n, vocab=500, seq_len=64, n_classes=5, seed=0):
    """Token-id documents whose class is decodable from token statistics
    (news20 stand-in for TextClassifier)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, n).astype(np.int32)
    # class-specific token ranges interleaved with shared noise (markers
    # span the whole document so recurrent encoders see them near the end)
    docs = rng.integers(1, vocab, (n, seq_len))
    for c in range(n_classes):
        rows = labels == c
        marker = 1 + c * (vocab // n_classes) + \
            rng.integers(0, vocab // n_classes, (int(rows.sum()),
                                                 seq_len // 2))
        docs[rows, ::2] = marker
    return docs.astype(np.float32), labels


def taxi_like(n, seed=0):
    """NYC-taxi-style univariate series with daily seasonality + anomalies
    (reference: apps/anomaly-detection notebook)."""
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    series = (10 + 5 * np.sin(2 * np.pi * t / 48) +
              rng.normal(0, 0.5, n)).astype(np.float32)
    anomalies = rng.choice(n, size=max(n // 50, 1), replace=False)
    series[anomalies] += rng.choice([-8, 8], size=anomalies.size)
    return series


# -- real reference mini-datasets -----
# The reference repo's own test fixtures sit in-tree; every loader
# degrades to None so the examples keep their synthetic fallback when the
# reference checkout is absent.

REF_RESOURCES = "/root/reference/pyzoo/test/zoo/resources"


def reference_resource(*parts):
    path = os.path.join(os.environ.get("ZOO_REF_RESOURCES", REF_RESOURCES),
                        *parts)
    return path if os.path.exists(path) else None


def movielens_real():
    """The reference's real MovieLens slice (recommender/data.parquet,
    458 rows: userId, itemId, 1-5 rating + gender/age/occupation/genres).
    Returns a pandas DataFrame or None."""
    path = reference_resource("recommender", "data.parquet")
    if path is None:
        return None
    try:
        import pandas as pd
        return pd.read_parquet(path)
    except Exception:
        return None


def glove_real():
    """Path to the reference's real GloVe 6B.50d subset, or None."""
    return reference_resource("glove.6B", "glove.6B.50d.txt")


def cat_dog_real():
    """Root of the reference's real cats/dogs JPEG fixture, or None."""
    return reference_resource("cat_dog")
