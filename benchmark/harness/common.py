"""Small shared pieces: files found by name, model sizes."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(kind: str, name: str, root: str = ROOT) -> dict:
    """``<root>/<kind>/<name>.json``: one cell, configuration, traffic mix
    or metric is one file, found by the name BENCHMARK.json gives it."""
    path = os.path.join(root, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT):
    """``<root>/<kind>/<name>.py`` imported by path: a new driver,
    reader or reference is a new file, never an edit."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"zoo_benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_files(root: str = ROOT):
    """Every per-layer metric file, as (name, dict)."""
    d = os.path.join(root, "metrics")
    return [(f[:-5], load_json("metrics", f[:-5], root))
            for f in sorted(os.listdir(d)) if f.endswith(".json")]


def sizes(cfg: dict) -> dict:
    """A configuration's sizes under the names the harness computes with
    (the published keys of the BERT family; another family's file maps
    its own keys here through a new driver)."""
    return {
        "hidden_size": cfg["hidden_size"],
        "num_hidden_layers": cfg["num_hidden_layers"],
        "num_attention_heads": cfg["num_attention_heads"],
        "intermediate_size": cfg["intermediate_size"],
        "vocab_size": cfg["vocab_size"],
        "positions": cfg["max_position_embeddings"],
        "num_labels": cfg.get("num_labels", 0),
    }
