"""Keras-style model topology: Sequential / Model / KerasNet.

Parity surface: ``zoo/.../pipeline/api/keras/models/Topology.scala`` —
``KerasNet`` (compile:135, fit:343, evaluate, predict, setTensorBoard:204,
setCheckpoint:245, gradient clipping:261-294), ``Model``:602,
``Sequential``:825 — and the python mirror
``pyzoo/zoo/pipeline/api/keras/engine/topology.py``.

TPU redesign: ``compile`` builds an :class:`SPMDTrainer` whose jitted step is
the whole iteration (forward+backward+psum+update in one XLA program); both
containers are themselves :class:`KerasLayer` so they nest and can be called
on symbolic Variables (weight sharing included).
"""

from __future__ import annotations

import json
import logging
import os
import pickle
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .....common.zoo_trigger import EveryEpoch, MaxEpoch, ZooTrigger
from .....common.nncontext import get_nncontext
from .....feature.feature_set import ArrayFeatureSet, FeatureSet
from .....pipeline.engine import GradientClipping, SPMDTrainer
from .....utils import serialization, tensorboard
from .....utils.telemetry import span
from ..metrics import get_metric
from ..objectives import get_loss
from ..optimizers import get_optimizer
from .base import InputLayer, KerasLayer
from .graph import GraphFunction, Node, Variable


def to_feature_set(x, y=None) -> FeatureSet:
    if isinstance(x, FeatureSet):
        return x
    if hasattr(x, "to_feature_set"):  # ImageSet / TextSet / DataFrames
        return x.to_feature_set()
    return ArrayFeatureSet(x, y)


def _apply_layer_chain(layers, params, x, state, training, rng):
    """Shared sequential-application logic for containers."""
    new_state = {}
    state = state or {}
    for layer in layers:
        p = params.get(layer.name, {}) if params else {}
        kwargs: Dict[str, Any] = {}
        if layer.has_state:
            kwargs["state"] = state.get(layer.name, {})
        if layer.stochastic:
            layer_rng = None
            if rng is not None:
                rng, layer_rng = jax.random.split(rng)
            kwargs["rng"] = layer_rng
        out = layer.call(p, x, training=training, **kwargs)
        if layer.has_state:
            out, s = out
            new_state[layer.name] = s
        x = out
    return x, new_state


class KerasNet(KerasLayer):
    """Common training surface for Sequential and Model."""

    has_state = True
    stochastic = True

    def __init__(self, name=None):
        super().__init__(name=name)
        self.optimizer = None
        self.loss = None
        self.metrics: List = []
        self.trainer: Optional[SPMDTrainer] = None
        self._clipping = GradientClipping()
        self._checkpoint_dir = None
        self._checkpoint_trigger: Optional[ZooTrigger] = None
        self._tb: Optional[tuple] = None
        self._compute_dtype = None
        self._frozen: set = set()

    # -- abstract ------------------------------------------------------
    def graph_function(self) -> GraphFunction:
        raise NotImplementedError

    # -- config --------------------------------------------------------
    def compile(self, optimizer, loss, metrics=None):
        """Parity: Topology.scala:135 / topology.py compile."""
        self.optimizer = get_optimizer(optimizer)
        self.loss = get_loss(loss)
        self.metrics = [get_metric(m, self.loss) for m in (metrics or [])]
        self.trainer = None  # rebuild on next fit
        return self

    def set_constant_gradient_clipping(self, min_value, max_value):
        self._clipping = GradientClipping(min_value=min_value,
                                          max_value=max_value)

    def set_gradient_clipping_by_l2_norm(self, clip_norm):
        self._clipping = GradientClipping(l2_norm=clip_norm)

    def clear_gradient_clipping(self):
        self._clipping = GradientClipping()

    def set_tensorboard(self, log_dir, app_name):
        self._tb = (log_dir, app_name)

    def get_train_summary(self, tag=None):
        if not self._tb:
            return []
        return tensorboard.read_scalars(
            os.path.join(self._tb[0], self._tb[1], "train"), tag)

    def get_validation_summary(self, tag=None):
        if not self._tb:
            return []
        return tensorboard.read_scalars(
            os.path.join(self._tb[0], self._tb[1], "validation"), tag)

    def set_checkpoint(self, path, over_write=True,
                       trigger: Optional[ZooTrigger] = None):
        self._checkpoint_dir = path
        self._checkpoint_trigger = trigger or EveryEpoch()

    def set_evaluate_status(self):  # parity no-op (eval uses training=False)
        return self

    def set_compute_dtype(self, dtype):
        """TPU-specific: run forward/backward in bfloat16 (params stay f32)."""
        self._compute_dtype = dtype
        self.trainer = None
        return self

    # -- trainer plumbing ---------------------------------------------
    def _ensure_trainer(self) -> SPMDTrainer:
        if self.trainer is not None:
            return self.trainer
        graph = self.graph_function()
        old_params = None
        old_state = None
        if getattr(self, "_built_params", None) is not None:
            old_params, old_state = self._built_params

        def apply_fn(params, inputs, state, training, rng):
            return graph.apply(params, inputs, state=state, training=training,
                               rng=rng, collect_state=True)

        def init_fn(rng):
            with span("model/build"):
                return graph.init(rng)

        optimizer = self.optimizer or get_optimizer("sgd")
        loss = self.loss if self.loss is not None else get_loss("mse")
        sharding_fn = self._resolve_param_sharding_fn(graph)
        self.trainer = SPMDTrainer(
            apply_fn, init_fn, loss, optimizer, metrics=self.metrics,
            compute_dtype=self._compute_dtype, clipping=self._clipping,
            param_sharding_fn=sharding_fn)
        if old_params is not None:
            self.trainer.set_params(old_params, old_state)
        if self._checkpoint_dir:
            self.trainer.checkpoint_dir = self._checkpoint_dir
            self.trainer.checkpoint_trigger = self._checkpoint_trigger
        if self._tb:
            self.trainer.train_summary = tensorboard.TrainSummary(*self._tb)
            self.trainer.val_summary = tensorboard.ValidationSummary(
                *self._tb)
        if self._frozen:
            self.trainer.set_frozen(self._frozen)
        return self.trainer

    # -- freeze / transfer learning (GraphNet freeze/unFreeze parity) --
    def freeze(self, names: Optional[Sequence[str]] = None):
        """Exclude layers from training (all layers when ``names`` is
        None). Parity: ``GraphNet.freeze`` (NetUtils.scala)."""
        layer_names = {l.name for l in self.graph_function().layers}
        if names is None:
            self._frozen = set(layer_names)
        else:
            unknown = set(names) - layer_names
            if unknown:
                raise ValueError(f"unknown layers: {sorted(unknown)}")
            self._frozen |= set(names)
        if self.trainer is not None:
            self.trainer.set_frozen(self._frozen)
        return self

    def unfreeze(self, names: Optional[Sequence[str]] = None):
        if names is None:
            self._frozen = set()
        else:
            self._frozen -= set(names)
        if self.trainer is not None:
            self.trainer.set_frozen(self._frozen)
        return self

    def freeze_up_to(self, *names: str):
        """Freeze every layer from the inputs up to (and including) the
        named layers (parity: ``GraphNet.freezeUpTo``)."""
        graph = self.graph_function()
        nodes_by_layer = {}
        for node in graph.nodes:
            nodes_by_layer.setdefault(node.layer.name, []).append(node)
        unknown = set(names) - set(nodes_by_layer)
        if unknown:
            raise ValueError(f"unknown layers: {sorted(unknown)}")
        target = set()
        visited = set()
        stack = [n for name in names for n in nodes_by_layer[name]]
        while stack:
            node = stack.pop()
            if node.id in visited:
                continue
            visited.add(node.id)
            target.add(node.layer.name)
            for v in node.inputs:
                if v.node is not None:
                    stack.append(v.node)
        return self.freeze(sorted(target))

    def frozen_layers(self) -> List[str]:
        return sorted(self._frozen)

    def set_param_sharding(self, fn):
        """Install a params->shardings fn (see parallel.sharding)."""
        self._param_sharding_fn = fn
        self.trainer = None

    def _resolve_param_sharding_fn(self, graph):
        """Single precedence rule for BOTH training surfaces (Model.fit
        and the Estimator): explicit set_param_sharding wins; otherwise
        the config-driven layout (ZooConfig.param_sharding)."""
        fn = getattr(self, "_param_sharding_fn", None)
        if fn is not None:
            return fn
        return self._config_param_sharding(graph)

    def _config_param_sharding(self, graph):
        """Config-driven default layout (ZooConfig.param_sharding) when no
        explicit set_param_sharding() was given: "auto" applies the
        annotation-driven rules whenever the ambient mesh has a non-data
        axis > 1; "fsdp" also shards embed-annotated params over the
        data axis (ZeRO-3 style); "none" keeps the explicit-only
        contract."""
        from .....common import nncontext as _nn

        ctx = _nn._global_context
        if ctx is None:
            return None
        mode = str(getattr(ctx.config, "param_sharding", "auto")).lower()
        if mode not in ("auto", "none", "default", "fsdp"):
            raise ValueError(
                f"param_sharding must be auto|none|default|fsdp, "
                f"got {mode!r}")
        if mode == "none":
            return None
        shape = dict(ctx.mesh.shape)
        non_data = any(v > 1 for ax, v in shape.items() if ax != "data")
        if mode == "auto" and not non_data:
            return None
        from .....parallel.sharding import (FSDP_RULES,
                                            make_param_sharding_fn)

        rules = FSDP_RULES if mode == "fsdp" else None
        return make_param_sharding_fn(graph, ctx.mesh, rules=rules)

    # -- training surface ---------------------------------------------
    def fit(self, x, y=None, batch_size=32, nb_epoch=10,
            validation_data=None, distributed=True,
            checkpoint_trigger=None):
        trainer = self._ensure_trainer()
        train_set = to_feature_set(x, y)
        val_set = None
        if validation_data is not None:
            if isinstance(validation_data, tuple):
                val_set = to_feature_set(*validation_data)
            else:
                val_set = to_feature_set(validation_data)
        end_epoch = trainer.epoch + nb_epoch
        trainer.train(train_set, batch_size,
                      end_trigger=MaxEpoch(end_epoch),
                      checkpoint_trigger=checkpoint_trigger,
                      validation_set=val_set)
        self._built_params = (trainer.params, trainer.net_state)
        return self

    def evaluate(self, x, y=None, batch_size=32):
        trainer = self._ensure_trainer()
        results = trainer.evaluate(to_feature_set(x, y), batch_size)
        self._built_params = (trainer.params, trainer.net_state)
        return results

    def predict(self, x, batch_size=128, distributed=True):
        trainer = self._ensure_trainer()
        if isinstance(x, FeatureSet):
            data = x
        elif hasattr(x, "to_feature_set"):
            data = x.to_feature_set()
        else:
            data = ArrayFeatureSet(x)
        out = trainer.predict(data, batch_size)
        self._built_params = (trainer.params, trainer.net_state)
        return out

    def predict_classes(self, x, batch_size=128, zero_based_label=True):
        probs = self.predict(x, batch_size)
        classes = np.argmax(probs, axis=-1)
        return classes if zero_based_label else classes + 1

    # -- weights -------------------------------------------------------
    def _params_tuple(self):
        if self.trainer is not None and self.trainer.params is not None:
            return self.trainer.params, self.trainer.net_state
        if getattr(self, "_built_params", None) is not None:
            return self._built_params
        # build eagerly
        trainer = self._ensure_trainer()
        trainer.ensure_initialized()
        self._built_params = (trainer.params, trainer.net_state)
        return self._built_params

    def get_weights(self) -> List[np.ndarray]:
        params, _ = self._params_tuple()
        return [np.asarray(l) for l in jax.tree_util.tree_leaves(params)]

    def set_weights(self, weights: Sequence[np.ndarray]):
        params, state = self._params_tuple()
        treedef = jax.tree_util.tree_structure(params)
        leaves = jax.tree_util.tree_leaves(params)
        assert len(leaves) == len(weights), \
            f"expected {len(leaves)} arrays, got {len(weights)}"
        with span("model/set_weights", leaves=len(leaves)):
            new_leaves = [jnp.asarray(w, l.dtype) if hasattr(l, "dtype")
                          else w for w, l in zip(weights, leaves)]
            new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
            if self.trainer is None:
                self._built_params = (new_params, state)
            else:
                # the trainer places copies of its own: hold those, or a
                # second set of the weights stays on the device until the
                # next fit returns (a model sized to fill the chip cannot
                # load its step beside it)
                self.trainer.set_params(new_params, state)
                self._built_params = (self.trainer.params,
                                      self.trainer.net_state)

    def get_params(self):
        return self._params_tuple()[0]

    def get_state(self):
        """The layers' non-trainable state, by layer name: what a training
        step writes without a gradient (docs/training.md)."""
        return self._params_tuple()[1]

    def set_state(self, state):
        """Replace the layers' state with a tree of ``get_state``'s
        structure; the weights stay."""
        trainer = self._ensure_trainer()
        trainer.set_state(state)
        self._built_params = (trainer.params, trainer.net_state)

    # -- persistence ---------------------------------------------------
    def save_model(self, path, weight_path=None, over_write=False):
        """Saves architecture (definition JSON: layer classes + captured
        configs + DAG connectivity, ``engine/model_io.py``) + weights (npz).

        Parity: ``KerasNet.saveModel`` (Topology.scala:109) — the reference
        also persists a language-neutral module graph, not a pickled
        object. Graphs holding arbitrary callables (Lambda/CustomLoss)
        fall back to pickle with a warning.
        """
        from . import model_io

        if os.path.exists(path) and not over_write:
            raise IOError(f"{path} exists; pass over_write=True")
        os.makedirs(path, exist_ok=True)
        # a re-save may switch formats (json <-> pickle fallback); stale
        # artifacts of the other format would shadow the fresh ones at
        # load time, pairing the wrong architecture with the new weights
        for stale in ("architecture.json", "config_arrays.npz",
                      "architecture.pkl"):
            sp = os.path.join(path, stale)
            if os.path.exists(sp):
                os.remove(sp)
        try:
            spec, arrays = model_io.graph_to_spec(self.graph_function(),
                                                  self.name)
            with open(os.path.join(path, "architecture.json"), "w") as f:
                json.dump(spec, f, indent=1)
            if arrays:
                np.savez(os.path.join(path, "config_arrays.npz"), **arrays)
        except model_io.UnserializableConfig as e:
            logging.getLogger("analytics_zoo_tpu").warning(
                "definition serialization unavailable (%s); falling back "
                "to pickle", e)
            trainer = self.trainer
            self.trainer = None  # strip unpicklable runtime
            tb, self._tb = self._tb, None
            try:
                with open(os.path.join(path, "architecture.pkl"),
                          "wb") as f:
                    pickle.dump(self, f)
            finally:
                self.trainer = trainer
                self._tb = tb
        params, state = self._params_tuple()
        serialization.save_pytree(
            os.path.join(path, "weights.npz"),
            {"params": serialization.tree_to_numpy(params),
             "state": serialization.tree_to_numpy(state)})

    saveModel = save_model

    @staticmethod
    def load_model(path, weight_path=None):
        from . import model_io

        json_path = os.path.join(path, "architecture.json")
        if os.path.exists(json_path):
            with open(json_path) as f:
                spec = json.load(f)
            arrays = {}
            arr_path = os.path.join(path, "config_arrays.npz")
            if os.path.exists(arr_path):
                with np.load(arr_path, allow_pickle=False) as z:
                    arrays = {k: z[k] for k in z.files}
            model = model_io.spec_to_model(spec, arrays)
        else:  # pre-v1 checkpoints / Lambda fallback
            with open(os.path.join(path, "architecture.pkl"), "rb") as f:
                model = pickle.load(f)
        blob = serialization.load_pytree(os.path.join(path, "weights.npz"))
        model._built_params = (blob["params"], blob.get("state") or {})
        return model

    def export_tf(self, path, batch_size: Optional[int] = None):
        """Export inference as a TensorFlow SavedModel via ``jax2tf``
        (parity: ``saveToTf``, Topology.scala:568 / util/tf.py export_tf:
        the reference freezes a TF graph for serving interop)."""
        import tensorflow as tf  # noqa: F401 - required for export
        from jax.experimental import jax2tf

        self._ensure_trainer().ensure_initialized()
        trainer = self.trainer
        params = jax.tree.map(np.asarray, trainer.params)
        net_state = jax.tree.map(np.asarray, trainer.net_state)
        graph = self.graph_function()

        def infer(params, *inputs):
            return graph.apply(params, list(inputs), state=net_state,
                               training=False)

        graph_inputs = graph.inputs
        if batch_size is None:
            # symbolic batch dim through jax2tf shape polymorphism
            poly = [None] + [
                "b, " + ", ".join("_" for _ in v.shape[1:])
                if len(v.shape) > 1 else "b" for v in graph_inputs]
        else:
            poly = None
        tf_fn = jax2tf.convert(infer, polymorphic_shapes=poly)
        module = tf.Module()
        module.params = jax.tree.map(tf.Variable, params)
        in_specs = [
            tf.TensorSpec([batch_size] + [d for d in v.shape[1:]],
                          tf.as_dtype(np.float32), name=v.name)
            for v in graph_inputs]

        @tf.function(autograph=False, input_signature=in_specs)
        def serving_fn(*inputs):
            return tf_fn(module.params, *inputs)

        module.serving = serving_fn
        tf.saved_model.save(module, path,
                            signatures={"serving_default": serving_fn})
        return path

    saveToTf = export_tf

    # -- introspection -------------------------------------------------
    def summary(self, line_length=100):
        graph = self.graph_function()
        params, state = self._params_tuple()
        lines = [f'Model: "{self.name}"', "_" * line_length,
                 f"{'Layer (type)':40s}{'Param #':>12s}", "=" * line_length]
        total = 0
        for layer in graph.layers:
            p = params.get(layer.name, {})
            n = sum(int(np.prod(np.shape(l)))
                    for l in jax.tree_util.tree_leaves(p))
            total += n
            lines.append(f"{layer.name + ' (' + type(layer).__name__ + ')':40s}"
                         f"{n:>12,d}")
        lines += ["=" * line_length, f"Total params: {total:,d}"]
        text = "\n".join(lines)
        print(text)
        return text


class Model(KerasNet):
    """Functional graph container (Topology.scala:602)."""

    def __init__(self, input, output, name=None):
        super().__init__(name=name)
        self.inputs = [input] if isinstance(input, Variable) else list(input)
        self.outputs = [output] if isinstance(output, Variable) \
            else list(output)
        self._graph = GraphFunction(self.inputs, self.outputs)
        self.num_outputs = len(self.outputs)

    def graph_function(self):
        return self._graph

    # used as a nested layer -------------------------------------------
    def build(self, rng, input_shape):
        params, state = self._graph.init(rng)
        self._nested_state_template = state
        return params

    def init_state(self, input_shape):
        return getattr(self, "_nested_state_template", {})

    def call(self, params, inputs, training=False, state=None, rng=None):
        out, new_state = self._graph.apply(
            params, inputs, state=state, training=training, rng=rng,
            collect_state=True)
        return out, new_state

    def compute_output_shape(self, input_shape):
        shapes = [v.shape for v in self.outputs]
        return shapes[0] if len(shapes) == 1 else shapes

    def new_graph(self, outputs: Sequence[str]) -> "Model":
        """Graph surgery: re-root on named layers' outputs (parity:
        NetUtils GraphNet.newGraph). ``"layer"`` selects output 0 of that
        layer; ``"layer:k"`` selects output ``k`` of a multi-output layer
        (every output index is addressable — the round-2 last-var-per-layer
        map could only reach whichever variable happened to be walked
        last)."""
        graph = self._graph
        nodes_by_layer: Dict[str, Any] = {}
        vars_by_layer: Dict[str, Dict[int, Variable]] = {}
        for node in graph.nodes:
            nodes_by_layer.setdefault(node.layer.name, node)
            for v in _node_out_vars(node, graph):
                vars_by_layer.setdefault(node.layer.name, {})[v.index] = v
        outs = []
        for name in outputs:
            index = 0
            if ":" in name:
                name, idx_s = name.rsplit(":", 1)
                index = int(idx_s)
            node = nodes_by_layer.get(name)
            if node is None:
                raise ValueError(
                    f"no layer named {name!r} in the graph "
                    f"(have: {sorted(nodes_by_layer)})")
            v = vars_by_layer.get(name, {}).get(index)
            if v is None:
                v = _make_out_var(node, index)
            outs.append(v)
        return Model(self.inputs, outs if len(outs) > 1 else outs[0],
                     name=self.name + "_sub")


def _layer_out_shapes(node):
    shape = node.layer.compute_output_shape(
        node.inputs[0].shape if len(node.inputs) == 1
        else [v.shape for v in node.inputs])
    if node.layer.num_outputs > 1:
        return list(shape)
    return [shape]


def _make_out_var(node, index: int) -> Variable:
    shapes = _layer_out_shapes(node)
    if index >= len(shapes):
        raise ValueError(
            f"layer {node.layer.name!r} has {len(shapes)} outputs; "
            f"index {index} out of range")
    return Variable(node, shapes[index], index=index)


def _node_out_vars(node, graph):
    """Variables produced by ``node`` that are materialized in the graph
    (as other nodes' inputs or as graph outputs)."""
    seen = []
    for v in graph.outputs:
        if v.node is node:
            seen.append(v)
    for n in graph.nodes:
        for v in n.inputs:
            if v.node is node and v not in seen:
                seen.append(v)
    if not seen:
        seen.append(_make_out_var(node, 0))
    return seen


class Sequential(KerasNet):
    """Linear stack (Topology.scala:825)."""

    def __init__(self, name=None):
        super().__init__(name=name)
        self.layers: List[KerasLayer] = []

    def add(self, layer) -> "Sequential":
        if not self.layers and not isinstance(layer, (Sequential, Model)):
            if layer.input_shape is None and not isinstance(layer, InputLayer):
                raise ValueError(
                    "first layer needs input_shape (parity with reference "
                    "Sequential semantics)")
        self.layers.append(layer)
        return self

    def _input_shape(self):
        first = self.layers[0]
        if isinstance(first, Sequential):
            return first._input_shape()
        if isinstance(first, Model):
            shapes = [v.shape for v in first.inputs]
            return shapes[0] if len(shapes) == 1 else shapes
        return first.input_shape

    def graph_function(self):
        in_shape = self._input_shape()
        inp = Variable(None, in_shape, name=self.name + "_input")
        x = inp
        for layer in self.layers:
            x = layer(x)
        return GraphFunction([inp], [x])

    def to_model(self) -> "Model":
        """Sequential -> functional Model over the same layer objects
        (parity: ``Sequential.toModel``, Topology.scala:914). Weights are
        carried across; graph surgery (new_graph/freeze_up_to) then
        applies."""
        graph = self.graph_function()
        m = Model(graph.inputs, graph.outputs
                  if len(graph.outputs) > 1 else graph.outputs[0],
                  name=self.name + "_model")
        if getattr(self, "_built_params", None) is not None or \
                self.trainer is not None:
            # host-materialize: the live device arrays are donated into the
            # source model's next train step (deleted), which would leave
            # the derived model aliasing dead buffers
            m._built_params = jax.tree.map(np.asarray, self._params_tuple())
        m.optimizer, m.loss, m.metrics = (self.optimizer, self.loss,
                                          self.metrics)
        return m

    toModel = to_model

    def new_graph(self, outputs: Sequence[str]) -> "Model":
        return self.to_model().new_graph(outputs)

    def save_keras2(self, path: str) -> str:
        """Write a runnable Keras-2 python definition of this stack
        (parity: ``saveToKeras2``, Topology.scala:557)."""
        from .keras2_export import sequential_to_keras2_source

        src = sequential_to_keras2_source(self)
        with open(path, "w") as f:
            f.write(src)
        return path

    saveToKeras2 = save_keras2

    # used as a nested layer -------------------------------------------
    def build(self, rng, input_shape):
        params = {}
        shape = input_shape
        for layer in self.layers:
            rng, sub = jax.random.split(rng)
            p = layer.build(sub, shape)
            if p:
                params[layer.name] = p
            shape = layer.compute_output_shape(shape)
        return params

    def init_state(self, input_shape):
        state = {}
        shape = input_shape
        for layer in self.layers:
            s = layer.init_state(shape)
            if s:
                state[layer.name] = s
            shape = layer.compute_output_shape(shape)
        return state

    def call(self, params, inputs, training=False, state=None, rng=None):
        return _apply_layer_chain(self.layers, params, inputs, state,
                                  training, rng)

    def compute_output_shape(self, input_shape):
        shape = input_shape
        for layer in self.layers:
            shape = layer.compute_output_shape(shape)
        return shape
