"""Definition-based model persistence (no pickle).

The reference saves models as a language-neutral module graph (BigDL
protobuf via ``ZooModel.saveModel`` / ``Topology.scala:109``); round 1/2
here pickled the python object, which breaks on any class rename. This
module serializes the *definition*: every layer's class path + captured constructor config (``KerasLayer`` records
bound ``__init__`` args automatically) plus the Variable-DAG connectivity,
as JSON — rebuildable across refactors, diffable, and not a code-execution
vector. ndarray-valued config entries (e.g. embedding weight tables) go to
a sidecar npz.

Layers whose configs hold arbitrary callables (``Lambda``/``CustomLoss``)
are not definition-serializable; ``save_model`` falls back to pickle for
those graphs with a warning.
"""

from __future__ import annotations

import importlib
import json
import logging
from typing import Any, Dict, List

import numpy as np

logger = logging.getLogger("analytics_zoo_tpu.model_io")

FORMAT = "zoo-tpu-graph-v1"
_ALLOWED_PREFIX = "analytics_zoo_tpu."


class UnserializableConfig(Exception):
    pass


def _class_path(obj) -> str:
    cls = type(obj)
    return f"{cls.__module__}.{cls.__qualname__}"


def _encode(value, arrays: Dict[str, np.ndarray], path: str):
    from .base import KerasLayer

    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    if isinstance(value, (np.integer,)):
        return int(value)
    if isinstance(value, (np.floating,)):
        return float(value)
    if isinstance(value, np.ndarray) or hasattr(value, "__array__") and \
            not isinstance(value, (list, tuple, dict)):
        key = f"{path}_{len(arrays)}"
        arrays[key] = np.asarray(value)
        return {"__ndarray__": key}
    if isinstance(value, tuple):
        return {"__tuple__": [_encode(v, arrays, path) for v in value]}
    if isinstance(value, list):
        return [_encode(v, arrays, path) for v in value]
    if isinstance(value, dict):
        return {str(k): _encode(v, arrays, f"{path}.{k}")
                for k, v in value.items()}
    if isinstance(value, KerasLayer):
        return {"__layer__": _layer_spec(value, arrays)}
    raise UnserializableConfig(
        f"config entry {path!r} of type {type(value).__name__} cannot be "
        "serialized definition-wise (Lambda/CustomLoss graphs fall back "
        "to pickle)")


def _decode(value, arrays: Dict[str, np.ndarray]):
    if isinstance(value, dict):
        if "__ndarray__" in value:
            return arrays[value["__ndarray__"]]
        if "__tuple__" in value:
            return tuple(_decode(v, arrays) for v in value["__tuple__"])
        if "__layer__" in value:
            return _build_layer(value["__layer__"], arrays)
        return {k: _decode(v, arrays) for k, v in value.items()}
    if isinstance(value, list):
        return [_decode(v, arrays) for v in value]
    return value


def _layer_spec(layer, arrays) -> Dict[str, Any]:
    cfg = {k: v for k, v in getattr(layer, "_config", {}).items()
           if k not in ("name",)}
    return {"class": _class_path(layer), "name": layer.name,
            "config": {k: _encode(v, arrays, f"{layer.name}.{k}")
                       for k, v in cfg.items()}}


def _build_layer(spec: Dict[str, Any], arrays):
    path = spec["class"]
    if not path.startswith(_ALLOWED_PREFIX):
        raise ValueError(f"refusing to import layer class {path!r} "
                         f"(outside {_ALLOWED_PREFIX})")
    mod_name, _, cls_name = path.rpartition(".")
    cls = getattr(importlib.import_module(mod_name), cls_name)
    config = {k: _decode(v, arrays) for k, v in spec["config"].items()}
    config["name"] = spec["name"]
    return cls(**config)


# ---------------------------------------------------------------------------


def graph_to_spec(graph, name: str):
    """GraphFunction -> (json-able spec, sidecar arrays)."""
    arrays: Dict[str, np.ndarray] = {}
    var_ids: Dict[int, List] = {}
    spec_inputs = []
    for i, v in enumerate(graph.inputs):
        var_ids[v.id] = ["input", i]
        spec_inputs.append({"shape": list(v.shape[1:]), "name": v.name})

    spec_nodes = []
    for n_idx, node in enumerate(graph.nodes):
        in_refs = [var_ids[pv.id] for pv in node.inputs]
        spec_nodes.append({"layer": node.layer.name, "in": in_refs})
        # register this node's output variables lazily: any Variable whose
        # .node is this node maps to ["node", n_idx, index]
        for other in graph.nodes:
            for pv in other.inputs:
                if pv.node is node:
                    var_ids[pv.id] = ["node", n_idx, pv.index]
        for v in graph.outputs:
            if v.node is node:
                var_ids[v.id] = ["node", n_idx, v.index]

    layers = {}
    for layer in graph.layers:
        layers[layer.name] = _layer_spec(layer, arrays)

    spec = {
        "format": FORMAT,
        "name": name,
        "inputs": spec_inputs,
        "layers": [layers[ln] for ln in
                   [layer.name for layer in graph.layers]],
        "nodes": spec_nodes,
        "outputs": [var_ids[v.id] for v in graph.outputs],
    }
    return spec, arrays


def spec_to_model(spec: Dict[str, Any], arrays: Dict[str, np.ndarray]):
    """Rebuild a functional ``Model`` from a spec."""
    from .base import Input
    from .topology import Model

    if spec.get("format") != FORMAT:
        raise ValueError(f"unknown model format {spec.get('format')!r}")
    layers = {s["name"]: _build_layer(s, arrays) for s in spec["layers"]}
    inputs = [Input(shape=tuple(s["shape"]), name=s["name"])
              for s in spec["inputs"]]

    node_outputs: List[Any] = []

    def resolve(ref):
        kind = ref[0]
        if kind == "input":
            return inputs[ref[1]]
        out = node_outputs[ref[1]]
        if isinstance(out, (list, tuple)):
            return out[ref[2]]
        return out

    for node_spec in spec["nodes"]:
        layer = layers[node_spec["layer"]]
        xs = [resolve(r) for r in node_spec["in"]]
        node_outputs.append(layer(xs[0] if len(xs) == 1 else xs))

    outputs = [resolve(r) for r in spec["outputs"]]
    model = Model(inputs, outputs if len(outputs) > 1 else outputs[0],
                  name=spec.get("name"))
    return model
