"""Estimator: the thin training facade over the SPMD engine.

Parity surface: ``zoo/.../pipeline/estimator/Estimator.scala``
(``AbstractEstimator`` trait :33, class :65, ``train``:118,
``evaluate``:163, gradient-clipping state machine :79-116) and the python
mirror ``pyzoo/zoo/pipeline/estimator/estimator.py``.

TPU redesign: instead of wrapping ``InternalDistriOptimizer`` (2 Spark jobs
per iteration over the BlockManager allreduce), the Estimator owns one
:class:`SPMDTrainer` whose jitted step compiles forward/backward/psum/update
into a single XLA program.  ``optim_methods`` may be a dict keyed by
top-level parameter-group name — the multi-optimizer parameterSplits
behavior of ``Topology.scala:1122-1143`` — realized as
``optax.multi_transform`` labels instead of (offset, length) slices into a
flat weight vector.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Union

import optax

from ...common.zoo_trigger import MaxEpoch, ZooTrigger
from ...feature.feature_set import FeatureSet
from ..api.keras.metrics import get_metric
from ..api.keras.objectives import get_loss
from ..api.keras.optimizers import ZooOptimizer, get_optimizer
from ..engine import GradientClipping, SPMDTrainer


class AbstractEstimator:
    """Parity: the ``AbstractEstimator`` trait (Estimator.scala:33-45)."""

    def train(self, train_set, criterion=None, end_trigger=None,
              checkpoint_trigger=None, validation_set=None,
              validation_method=None, batch_size=32):
        raise NotImplementedError

    def evaluate(self, validation_set, validation_method=None,
                 batch_size=32):
        raise NotImplementedError

    def close(self):
        pass


class MultiOptimizer(ZooOptimizer):
    """Per-parameter-group optimizers (Topology.scala:1122-1143 parity).

    ``methods`` maps a top-level param subtree name (layer name) to a
    :class:`ZooOptimizer`; unmatched subtrees fall back to ``default``.
    """

    def __init__(self, methods: Dict[str, ZooOptimizer],
                 default: Optional[ZooOptimizer] = None):
        super().__init__(lr=next(iter(methods.values())).lr)
        self.methods = {k: get_optimizer(v) for k, v in methods.items()}
        self.default = get_optimizer(default) if default is not None else \
            next(iter(self.methods.values()))

    def lr_schedule(self):
        return self.default.lr_schedule()

    def to_optax(self) -> optax.GradientTransformation:
        transforms = {k: m.to_optax() for k, m in self.methods.items()}
        transforms["__default__"] = self.default.to_optax()

        def label_fn(params):
            return {k: (k if k in self.methods else "__default__")
                    for k in params}

        return optax.multi_transform(transforms, label_fn)


def _by_count(names):
    """Auto-generated layer names (``dense_9``, ``dense_10``) in the order
    their counters ran, digits compared as numbers."""
    return sorted(names, key=lambda n: [int(t) if t.isdigit() else t
                                        for t in re.split(r"(\d+)", n)])


class Estimator(AbstractEstimator):
    """Train/evaluate any layer (KerasNet or raw KerasLayer) on FeatureSets.

    Parameters mirror the reference constructor
    (``Estimator.apply`` Estimator.scala:195-258 / estimator.py:30):
    ``model``, ``optim_methods`` (single optimizer, name, or dict of
    param-group → optimizer), ``model_dir`` (checkpoint directory).
    """

    def __init__(self, model, optim_methods: Union[None, str, ZooOptimizer,
                                                   Dict] = None,
                 model_dir: Optional[str] = None):
        self.model = model
        if isinstance(optim_methods, dict):
            self.optimizer = MultiOptimizer(
                {k: get_optimizer(v) for k, v in optim_methods.items()})
        else:
            self.optimizer = get_optimizer(optim_methods or "sgd")
        self.model_dir = model_dir
        self._clipping = GradientClipping()
        self.trainer: Optional[SPMDTrainer] = None

    # -- gradient clipping state machine (Estimator.scala:79-116) ------
    def clear_gradient_clipping(self):
        self._clipping = GradientClipping()
        self._invalidate()

    def set_constant_gradient_clipping(self, min, max):  # noqa: A002
        self._clipping = GradientClipping(min_value=min, max_value=max)
        self._invalidate()

    def set_l2_norm_gradient_clipping(self, clip_norm):
        self._clipping = GradientClipping(l2_norm=clip_norm)
        self._invalidate()

    def _invalidate(self):
        if self.trainer is not None:
            # keep learned params, rebuild the compiled step with new clip
            params, state = self.trainer.params, self.trainer.net_state
            self.trainer = None
            self._pending_params = (params, state)

    # -- trainer plumbing ----------------------------------------------
    def _ensure_trainer(self, criterion, validation_method) -> SPMDTrainer:
        metrics = [get_metric(m, criterion) for m in
                   (validation_method or [])]
        if self.trainer is not None:
            self.trainer.metrics = metrics or self.trainer.metrics
            # drop ALL compiled eval programs (per-batch and the fused
            # scan variants) so the new metric set is traced in
            self.trainer.invalidate_eval()
            return self.trainer

        graph = self.model.graph_function()

        def apply_fn(params, inputs, state, training, rng):
            return graph.apply(params, inputs, state=state, training=training,
                               rng=rng, collect_state=True)

        # one precedence rule shared with Model.fit (auto TP / fsdp)
        sharding_fn = self.model._resolve_param_sharding_fn(graph) \
            if hasattr(self.model, "_resolve_param_sharding_fn") else \
            getattr(self.model, "_param_sharding_fn", None)
        self.trainer = SPMDTrainer(
            apply_fn, graph.init, criterion, self.optimizer,
            metrics=metrics, clipping=self._clipping,
            param_sharding_fn=sharding_fn)
        if getattr(self.model, "_built_params", None) is not None:
            self.trainer.set_params(*self.model._built_params)
        if getattr(self, "_pending_params", None) is not None:
            self.trainer.set_params(*self._pending_params)
            self._pending_params = None
        if self.model_dir is not None:
            self.trainer.checkpoint_dir = self.model_dir
        return self.trainer

    # -- training surface (Estimator.scala:118-161) --------------------
    def train(self, train_set: FeatureSet, criterion=None, end_trigger=None,
              checkpoint_trigger=None, validation_set=None,
              validation_method=None, batch_size=32):
        criterion = get_loss(criterion or "mse")
        trainer = self._ensure_trainer(criterion, validation_method)
        trainer.loss_fn = criterion
        trainer.train(train_set, batch_size=batch_size,
                      end_trigger=end_trigger or MaxEpoch(1),
                      checkpoint_trigger=checkpoint_trigger,
                      validation_set=validation_set,
                      validation_trigger=(checkpoint_trigger
                                          if validation_set is not None
                                          else None))
        self._sync_model()
        return self

    def train_minibatch(self, train_set, criterion=None, end_trigger=None,
                        checkpoint_trigger=None, validation_set=None,
                        validation_method=None):
        """Pre-batched variant (estimatorTrainMiniBatch parity): the
        FeatureSet already yields MiniBatch; batch_size is taken from it."""
        first = next(iter(train_set.batches(1)), None) \
            if not hasattr(train_set, "batch_size") else None
        bs = getattr(train_set, "batch_size", None) or (
            len(first.weights) if first is not None else 32)
        return self.train(train_set, criterion, end_trigger,
                          checkpoint_trigger, validation_set,
                          validation_method, batch_size=bs)

    def train_imagefeature(self, train_set, criterion=None, end_trigger=None,
                           checkpoint_trigger=None, validation_set=None,
                           validation_method=None, batch_size=32):
        """ImageSet variant (estimatorTrainImageFeature parity)."""
        to_fs = getattr(train_set, "to_feature_set", None)
        fs = to_fs() if to_fs else train_set
        val = validation_set.to_feature_set() if (
            validation_set is not None and
            hasattr(validation_set, "to_feature_set")) else validation_set
        return self.train(fs, criterion, end_trigger, checkpoint_trigger,
                          val, validation_method, batch_size)

    def evaluate(self, validation_set, validation_method=None,
                 batch_size=32):
        criterion = get_loss(getattr(self.trainer, "loss_fn", None) or "mse")
        trainer = self._ensure_trainer(criterion, validation_method)
        return trainer.evaluate(validation_set, batch_size=batch_size)

    evaluate_minibatch = evaluate
    evaluate_imagefeature = evaluate

    def predict(self, data, batch_size=128):
        trainer = self._ensure_trainer(get_loss("mse"), None)
        return trainer.predict(data, batch_size=batch_size)

    def get_model(self):
        self._sync_model()
        return self.model

    def load_checkpoint(self, directory):
        trainer = self._ensure_trainer(get_loss("mse"), None)
        trainer.load_checkpoint(directory)
        self._remap_param_names(trainer)
        self._sync_model()
        return self

    def _remap_param_names(self, trainer):
        """Auto-generated layer names differ between model instances; align
        checkpointed top-level keys onto this model's keys by position (the
        reference resumes by positional weight copy, Module.load)."""
        import jax

        expected, expected_state = self.model.graph_function().init(
            jax.random.PRNGKey(0))
        got = trainer.params
        if set(got) == set(expected):
            return
        if len(got) != len(expected):
            def shapes(groups):
                return {name: [tuple(getattr(l, "shape", ()))
                               for l in jax.tree_util.tree_leaves(g)]
                        for name, g in groups.items()}
            raise ValueError(
                "checkpoint/model param-group count mismatch: checkpoint "
                f"has {len(got)} group(s) {shapes(got)}, model expects "
                f"{len(expected)} group(s) {shapes(expected)}; only in "
                f"checkpoint: {sorted(set(got) - set(expected))}, only in "
                f"model: {sorted(set(expected) - set(got))}")
        # both sides by (layer type, count), the order the names were
        # handed out in: jax keeps dict keys sorted as strings, which puts
        # dense_10 before dense_9
        remapped = {new: got[old] for new, old in
                    zip(_by_count(expected), _by_count(got))}
        state = trainer.net_state or {}
        new_state = {new: state[old] for new, old in
                     zip(_by_count(expected_state), _by_count(state))
                     } if state else state
        trainer.set_params(remapped, new_state)

    def _sync_model(self):
        if self.trainer is not None and self.trainer.params is not None:
            self.model._built_params = (self.trainer.params,
                                        self.trainer.net_state)

    def close(self):
        self.trainer = None
