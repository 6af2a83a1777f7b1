"""TransformerLayer and BERT.

Parity surface: ``keras/layers/TransformerLayer.scala`` (279 LoC; GPT-style
decoder blocks, post-LN, gelu, optional bidirectional) and
``keras/layers/BERT.scala`` (402 LoC; 4 inputs — token ids, positions,
segment ids, attention mask; outputs per-block sequence states + pooled
output; erf-based gelu; extended mask = (1-mask)*-10000).

TPU redesign: one KerasLayer owning all block params (pytree), attention via
the Pallas flash kernel (ops/attention.py), dropout fused in-jit, params
annotated with logical axes so ``parallel.sharding`` can lay them out over a
('data','model') mesh (qkv/mlp-in column-parallel, proj/mlp-out row-parallel
— Megatron layout, collectives inserted by XLA).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .....ops.attention import flash_attention_blhd
from .....ops.fused_dropout_ln import dropout_add_layer_norm
from ..engine.base import KerasLayer, init_tensor


def _normal(rng, shape, std):
    return std * jax.random.normal(rng, shape, jnp.float32)


def _dropout(x, p, rng, training):
    if not training or rng is None or p <= 0.0:
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def _dp_dropout_add_ln(x, resid, gamma, beta, rng, p_drop, training):
    """dropout_add_layer_norm, entered through a pure-dp shard_map when
    one is needed for the kernel to engage (see _dp_mesh). The key is
    folded with the shard index so dropout masks decorrelate across
    data shards."""
    dp = _dp_mesh(x.shape[0])
    if dp is None or not training or rng is None or p_drop <= 0.0:
        return dropout_add_layer_norm(x, resid, gamma, beta, rng,
                                      p_drop, training)
    from jax.sharding import PartitionSpec as P
    px = P("data", None, None)
    pv = P(None)

    def body(x_, r_, g_, b_, key_):
        key_ = jax.random.fold_in(key_, jax.lax.axis_index("data"))
        return dropout_add_layer_norm(x_, r_, g_, b_, key_, p_drop,
                                      training)

    # check_vma=False: pallas interpret mode cannot trace under the vma
    # checker (jax's own error suggests this flag), and the region is a
    # single elementwise+rowwise op — gradient correctness of the wrap
    # (incl. the replicated gamma/beta psum on transpose) is pinned by
    # test_dp_wrap_grad_parity on the 8-device mesh
    return jax.shard_map(body, mesh=dp, in_specs=(px, px, pv, pv, P()),
                      out_specs=px, check_vma=False)(
        x, resid, gamma, beta, rng)


def _dp_mesh(batch):
    """The active mesh when kernels need a shard_map to engage: pure
    data parallelism (>1 devices, every other axis 1), batch divisible,
    and not already inside a shard_map. Mosaic custom calls cannot be
    auto-partitioned (ops/attention.py mosaic_partition_ok), so under a
    dp>1 mesh the layer enters a fully-manual shard_map at its kernel
    sites itself — batch-parallel attention and dropout+add+LN are
    embarrassingly parallel, so the wrap is spec-exact (no resharding)
    and the XLA route inside computes identically when the shapes are
    not kernel-eligible. Mixed layouts (tp/pp/sp/ep) are handled by
    their own shard_map paths or the XLA route."""
    from .....common import nncontext as _nn
    ctx = _nn._global_context
    if ctx is None:
        return None
    sizes = dict(ctx.mesh.shape)
    dp = int(sizes.get("data", 1))
    if dp <= 1 or any(int(v) > 1 for k, v in sizes.items()
                      if k != "data"):
        return None
    if batch % dp != 0:
        return None
    if jax.sharding.get_abstract_mesh().axis_names:
        return None          # already inside a shard_map
    return ctx.mesh


class TransformerLayer(KerasLayer):
    """GPT-style transformer stack.

    Inputs: token ids ``(B, L)`` (positions are implicit arange, parity with
    the reference's position-offset embedding). Outputs
    ``[sequence_states, pooled]`` (or all block states + pooled when
    ``output_all_block``).
    """

    stochastic = True
    gelu_approximate = True  # TransformerLayer.scala uses the tanh approx

    def __init__(self, n_block, hidden_p_drop=0.1, attn_p_drop=0.1,
                 n_head=12, initializer_range=0.02, bidirectional=False,
                 output_all_block=False, intermediate_size=0,
                 vocab=40990, seq_len=77, hidden_size=768,
                 embedding_layer=None, moe_experts=0, moe_top_k=2,
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name)
        self.n_block = int(n_block)
        self.n_head = int(n_head)
        self.hidden_p_drop = hidden_p_drop
        self.attn_p_drop = attn_p_drop
        self.initializer_range = initializer_range
        self.bidirectional = bidirectional
        self.output_all_block = output_all_block
        self.vocab = int(vocab)
        self.seq_len = int(seq_len)
        self.hidden_size = int(hidden_size)
        self.embedding_layer = embedding_layer
        # moe_experts > 0 swaps each block's MLP for a SparseMoE (expert
        # parallelism reachable from the model zoo)
        self.moe_experts = int(moe_experts)
        self.moe_top_k = int(moe_top_k)
        self._moe = None
        if embedding_layer is not None:
            # custom embedding (reference API): hidden size comes from its
            # output shape; it consumes the non-mask inputs
            out_shape = embedding_layer.compute_output_shape(
                (None, self.seq_len))
            self.hidden_size = int(out_shape[-1])
        self.intermediate_size = int(intermediate_size) or \
            4 * self.hidden_size
        assert self.hidden_size % self.n_head == 0
        self.num_outputs = (self.n_block if output_all_block else 1) + 1

    # -- params --------------------------------------------------------
    def _embedding_params(self, rng):
        if self.embedding_layer is not None:
            return {"embedding": self.embedding_layer.build(
                rng, (None, self.seq_len))}
        r1, r2 = jax.random.split(rng)
        params = {
            "tok_emb": _normal(r1, (self.vocab, self.hidden_size),
                               self.initializer_range),
            "pos_emb": _normal(r2, (self.seq_len, self.hidden_size),
                               self.initializer_range),
        }
        self._annotate(tok_emb=("vocab", "embed"),
                       pos_emb=(None, "embed"))
        return params

    def _block_params(self, rng):
        h = self.hidden_size
        m = self.intermediate_size
        keys = jax.random.split(rng, 5)
        std = self.initializer_range
        p = {
            "qkv_w": _normal(keys[0], (h, 3 * h), std),
            "qkv_b": jnp.zeros((3 * h,)),
            "proj_w": _normal(keys[1], (h, h), std),
            "proj_b": jnp.zeros((h,)),
            "ln1_g": jnp.ones((h,)), "ln1_b": jnp.zeros((h,)),
            "ln2_g": jnp.ones((h,)), "ln2_b": jnp.zeros((h,)),
        }
        if self.moe_experts:
            p["moe"] = self._moe.build(keys[2], (None, self.seq_len, h))
        else:
            p.update({
                "mlp_in_w": _normal(keys[2], (h, m), std),
                "mlp_in_b": jnp.zeros((m,)),
                "mlp_out_w": _normal(keys[3], (m, h), std),
                "mlp_out_b": jnp.zeros((h,)),
            })
        return p

    def _block_axis_map(self):
        """Logical axes per block param (Megatron TP layout)."""
        axes = {
            "qkv_w": ("embed", "heads"), "qkv_b": ("heads",),
            "proj_w": ("heads", "embed"), "proj_b": (None,),
            "ln1_g": (None,), "ln1_b": (None,),
            "ln2_g": (None,), "ln2_b": (None,),
        }
        if self.moe_experts:
            for k, v in self._moe.param_axes().items():
                axes[f"moe/{k}"] = v
        else:
            axes.update({"mlp_in_w": ("embed", "mlp"),
                         "mlp_in_b": ("mlp",),
                         "mlp_out_w": ("mlp", "embed"),
                         "mlp_out_b": (None,)})
        return axes

    def _pp_stages(self) -> int:
        """Pipeline stages from the ambient context (0/1 = no pipelining).
        Peeks the global context without creating one."""
        from .....common import nncontext as _nn
        ctx = _nn._global_context
        if ctx is None:
            return 1
        return int(ctx.mesh.shape.get("pipe", 1))

    def build(self, rng, input_shape):
        if self.moe_experts and self._moe is None:
            from .moe import SparseMoE
            self._moe = SparseMoE(self.moe_experts,
                                  self.intermediate_size,
                                  top_k=self.moe_top_k)
        rngs = jax.random.split(rng, self.n_block + 2)
        params = self._embedding_params(rngs[0])
        pp = self._pp_stages()
        if pp > 1:
            # GPipe layout: block params stacked on a leading 'stage'-
            # annotated axis so each pipe rank holds only its blocks
            # (parallel/pipeline.py schedule, reachable from Model.fit)
            if self.n_block % pp:
                raise ValueError(
                    f"pipeline_parallel={pp} must divide n_block="
                    f"{self.n_block}")
            if self.output_all_block:
                raise ValueError(
                    "output_all_block=True is incompatible with "
                    "pipeline_parallel > 1 (intermediate block states "
                    "live on other pipe ranks); build with "
                    "output_all_block=False")
            per_block = [self._block_params(rngs[i + 1])
                         for i in range(self.n_block)]
            params["blocks"] = jax.tree.map(
                lambda *ls: jnp.stack(ls), *per_block)
            self._annotate(**{
                f"blocks/{k}": ("stage",) + tuple(v)
                for k, v in self._block_axis_map().items()})
        else:
            for i in range(self.n_block):
                params[f"block{i}"] = self._block_params(rngs[i + 1])
                self._annotate(**{
                    f"block{i}/{k}": v
                    for k, v in self._block_axis_map().items()})
        params["pooler_w"] = _normal(rngs[-1],
                                     (self.hidden_size, self.hidden_size),
                                     self.initializer_range)
        params["pooler_b"] = jnp.zeros((self.hidden_size,))
        return params

    # -- compute -------------------------------------------------------
    def _ln(self, x, g, b, eps=1e-5):
        from .....ops.layernorm import layer_norm
        return layer_norm(x, g, b, eps)

    def _gelu(self, x):
        return jax.nn.gelu(x, approximate=self.gelu_approximate)

    def _seq_parallel(self) -> int:
        from .....common import nncontext as _nn
        ctx = _nn._global_context
        if ctx is None:
            return 1
        return int(ctx.mesh.shape.get("seq", 1))

    def _attention(self, p, x, mask_bias, rng, training):
        b, l, h = x.shape
        nh = self.n_head
        d = h // nh
        with jax.named_scope("zoo_mixer_proj"):
            qkv = jnp.matmul(x, p["qkv_w"].astype(x.dtype)) + \
                p["qkv_b"].astype(x.dtype)
            q, k, v = (t.reshape(b, l, nh, d)
                       for t in jnp.split(qkv, 3, axis=-1))
        with jax.named_scope("zoo_attn_core"):
            o = self._attention_core(q, k, v, mask_bias)
        with jax.named_scope("zoo_mixer_proj"):
            o = o.reshape(b, l, h)
            if rng is not None:
                rng, sub = jax.random.split(rng)
                o = _dropout(o, self.attn_p_drop, sub, training)
            return jnp.matmul(o, p["proj_w"].astype(x.dtype)) + \
                p["proj_b"].astype(x.dtype)

    def _attention_core(self, q, k, v, mask_bias):
        """Softmax attention of (B, L, heads, head size) queries, keys and
        values, the same out: the flash kernels (or the blockwise route),
        across the ``seq`` mesh axis where there is one."""
        b, l, nh, _ = q.shape
        sp = self._seq_parallel()
        if sp > 1 and l % sp == 0:
            # sequence parallelism over the 'seq' mesh axis: ulysses
            # (all-to-all head/seq swap, full-L local attention — the
            # flash kernel's favourite shape) when the head count splits
            # across the axis, else the ppermute ring with O(L/sp) score
            # memory (parallel/ulysses.py, parallel/ring_attention.py;
            # key-padding bias rides along either way)
            from .....common.nncontext import get_nncontext
            from .....parallel.ring_attention import ring_attention_sharded
            from .....parallel.ulysses import ulysses_attention_sharded

            mode = str(getattr(get_nncontext().config,
                               "sequence_parallel_mode", "auto")).lower()
            if mode not in ("auto", "ring", "ulysses"):
                raise ValueError(
                    f"sequence_parallel_mode must be auto|ring|ulysses, "
                    f"got {mode!r}")
            use_ulysses = (mode == "ulysses" or
                           (mode == "auto" and nh % sp == 0))
            kb = None
            if mask_bias is not None:
                kb = jnp.broadcast_to(
                    mask_bias.reshape(mask_bias.shape[0], l),
                    (b, l)).astype(jnp.float32)
            sp_attn = ulysses_attention_sharded if use_ulysses \
                else ring_attention_sharded
            qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            o = sp_attn(qh, kh, vh, get_nncontext().mesh,
                        causal=not self.bidirectional,
                        kbias=kb).transpose(0, 2, 1, 3)
        else:
            attn = functools.partial(flash_attention_blhd,
                                     causal=not self.bidirectional)
            dp = _dp_mesh(b)
            if dp is None:
                o = attn(q, k, v, bias=mask_bias)
            else:
                from jax.sharding import PartitionSpec as P
                p4 = P("data", None, None, None)
                # check_vma=False: see _dp_dropout_add_ln
                operands = [q, k, v]
                in_specs = [p4, p4, p4]
                if mask_bias is not None:
                    operands.append(mask_bias)
                    in_specs.append(
                        P("data", *([None] * (mask_bias.ndim - 1)))
                        if mask_bias.shape[0] == b else
                        P(*([None] * mask_bias.ndim)))

                def body(q_, k_, v_, bias_=None):
                    return attn(q_, k_, v_, bias=bias_)

                o = jax.shard_map(
                    body, mesh=dp, in_specs=tuple(in_specs),
                    out_specs=p4, check_vma=False)(*operands)
        return o

    def _block(self, p, x, mask_bias, rng, training):
        # both residual sites run the fused dropout+add+LN op: one
        # bandwidth pass on the TPU kernel path (ops/fused_dropout_ln.py),
        # the exact pre-existing bernoulli+layer_norm composition
        # everywhere else
        r1 = r2 = r3 = None
        if rng is not None:
            r1, r2, r3 = jax.random.split(rng, 3)
        a = self._attention(p, x, mask_bias, r1, training)
        with jax.named_scope("zoo_norm"):
            n = _dp_dropout_add_ln(a, x, p["ln1_g"], p["ln1_b"], r2,
                                   self.hidden_p_drop, training)
        m = self._ffn(p, n, training)
        with jax.named_scope("zoo_norm"):
            return _dp_dropout_add_ln(m, n, p["ln2_g"], p["ln2_b"], r3,
                                      self.hidden_p_drop, training)

    def _ffn(self, p, n, training):
        if self.moe_experts:
            return self._moe.call(p["moe"], n, training=training)
        with jax.named_scope("zoo_dense_mlp"):
            m = jnp.matmul(n, p["mlp_in_w"].astype(n.dtype)) + \
                p["mlp_in_b"].astype(n.dtype)
            m = self._gelu(m)
            return jnp.matmul(m, p["mlp_out_w"].astype(n.dtype)) + \
                p["mlp_out_b"].astype(n.dtype)

    def _embed(self, params, inputs, rng, training):
        if self.embedding_layer is not None:
            x = inputs if not isinstance(inputs, (list, tuple)) or \
                len(inputs) > 1 else inputs[0]
            e = self.embedding_layer.call(params["embedding"], x,
                                          training=training)
            return e, None
        tokens = (inputs[0] if isinstance(inputs, (list, tuple))
                  else inputs).astype(jnp.int32)
        e = jnp.take(params["tok_emb"], tokens, axis=0)
        e = e + params["pos_emb"][None, :e.shape[1]]
        return e, None

    def _pooler(self, params, x):
        with jax.named_scope("zoo_head"):
            first = x[:, 0]
            return jnp.tanh(jnp.matmul(first, params["pooler_w"]
                                       .astype(x.dtype)) +
                            params["pooler_b"].astype(x.dtype))

    def _call_pp(self, params, e, mask_bias, rng, training):
        """Run the block trunk as a GPipe pipeline over the 'pipe' mesh
        axis (parallel/pipeline.py): the stacked block params are already
        sharded one stage per rank; activations + mask + dropout seed
        rotate along the ring as one pytree."""
        from .....common.nncontext import get_nncontext
        from .....parallel.pipeline import pipeline_forward

        ctx = get_nncontext()
        mesh = ctx.mesh
        S = int(mesh.shape["pipe"])
        bps = self.n_block // S
        n_micro = int(getattr(ctx.config, "pipeline_microbatches", 0)) or S
        b = e.shape[0]
        tree = {"x": e}
        if mask_bias is not None:
            tree["mask"] = jnp.broadcast_to(
                mask_bias, (b,) + tuple(mask_bias.shape[1:]))
        if rng is not None:
            seed = jax.random.randint(rng, (), 0, np.iinfo(np.int32).max)
            tree["seed"] = jnp.broadcast_to(seed, (b,))

        blocks = jax.tree.map(
            lambda l: l.reshape((S, bps) + l.shape[1:]), params["blocks"])

        def stage(p_local, t):
            x = t["x"]
            mask = t.get("mask")
            key = None
            if "seed" in t:
                key = jax.random.fold_in(
                    jax.random.PRNGKey(0), t["seed"][0])
                key = jax.random.fold_in(
                    key, jax.lax.axis_index("pipe"))

            def body(x, p_i):
                bp, i = p_i
                brng = (jax.random.fold_in(key, i)
                        if key is not None else None)
                return self._block(bp, x, mask, brng, training), None

            x, _ = jax.lax.scan(body, x, (p_local, jnp.arange(bps)))
            return dict(t, x=x)

        out = pipeline_forward(stage, blocks, tree, mesh,
                               n_microbatch=n_micro)
        return out["x"]

    # -- KV-cache incremental decode (ops/kv_cache.py) -----------------
    #
    # The generative-serving path: prefill runs the prompt once through
    # the standard causal flash/blockwise route and stashes every
    # block's projected K/V into preallocated slabs; decode_step then
    # advances one token per call with O(S) cached attention — the
    # step's jaxpr has no (L, L) contraction (``decode_step_is_cached``).
    # Decode is inference-only: no dropout, per-block param layout
    # (pipeline_parallel stacking is a training layout).

    def _require_decode_layout(self, params):
        if self.bidirectional:
            raise ValueError(
                "KV-cache decode needs a causal trunk; this layer was "
                "built bidirectional (BERT-style)")
        if "blocks" in params:
            raise ValueError(
                "KV-cache decode does not support the pipeline-parallel "
                "stacked-block layout; rebuild with pipeline_parallel=1")

    def init_decode_state(self, batch, capacity, dtype=jnp.float32,
                          rng=None):
        """Preallocate (B, S, H, D) K/V slabs for every block.
        ``dtype="int8"`` allocates quantized ``Int8KVSlab`` slabs — the
        cache ops dequantize inside the attention einsums, so prefill /
        decode_step / decode_chunk below run unchanged."""
        from .....ops.kv_cache import init_decode_state
        return init_decode_state(
            self.n_block, batch, capacity, self.n_head,
            self.hidden_size // self.n_head, dtype=dtype, rng=rng)

    def lm_logits(self, params, x):
        """Token logits via embedding weight tying: x @ tok_emb^T."""
        if self.embedding_layer is not None:
            raise ValueError("lm_logits needs the built-in token "
                             "embedding (weight tying)")
        with jax.named_scope("zoo_lm_head"):
            return jnp.matmul(x, params["tok_emb"].T.astype(x.dtype))

    def prefill(self, params, tokens, lengths, state):
        """Fill the cache from padded prompts; return last-token logits.

        tokens: (B, Lp) left-aligned prompt ids padded to a shared Lp;
        lengths: (B,) int32 true prompt lengths (the ragged tail is
        masked with a key bias). Returns (logits (B, vocab), state).
        """
        from .....ops.kv_cache import write_prompt
        self._require_decode_layout(params)
        tokens = tokens.astype(jnp.int32)
        b, lp = tokens.shape
        nh = self.n_head
        d = self.hidden_size // nh
        x = jnp.take(params["tok_emb"], tokens, axis=0)
        x = x + params["pos_emb"][None, :lp]
        # additive key bias over the padded tail, rides the flash route
        # exactly like BERT's attention_mask bias
        kb = jnp.where(jnp.arange(lp)[None, :] < lengths[:, None],
                       0.0, -1e9).astype(jnp.float32)
        kb = kb[:, None, None, :]
        k_caches, v_caches = [], []
        for i in range(self.n_block):
            p = params[f"block{i}"]
            qkv = jnp.matmul(x, p["qkv_w"].astype(x.dtype)) + \
                p["qkv_b"].astype(x.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q4, k4, v4 = (t.reshape(b, lp, nh, d) for t in (q, k, v))
            o = flash_attention_blhd(q4, k4, v4, bias=kb, causal=True)
            k_caches.append(write_prompt(state.k_cache[i], k4))
            v_caches.append(write_prompt(state.v_cache[i], v4))
            a = jnp.matmul(o.reshape(b, lp, self.hidden_size),
                           p["proj_w"].astype(x.dtype)) + \
                p["proj_b"].astype(x.dtype)
            n = _dp_dropout_add_ln(a, x, p["ln1_g"], p["ln1_b"], None,
                                   0.0, False)
            m = self._ffn(p, n, False)
            x = _dp_dropout_add_ln(m, n, p["ln2_g"], p["ln2_b"], None,
                                   0.0, False)
        last = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None].astype(
                jnp.int32), axis=1)[:, 0]
        state = state._replace(k_cache=tuple(k_caches),
                               v_cache=tuple(v_caches),
                               lengths=lengths.astype(jnp.int32))
        return self.lm_logits(params, last), state

    def decode_step(self, params, state, tokens):
        """Advance every slot one token: (B,) ids -> ((B, vocab), state).

        Appends each slot's K/V row at its own write offset and attends
        the single query row against the slab — O(S) per token, no
        full-sequence recompute.
        """
        from .....ops.kv_cache import cached_attention_step
        self._require_decode_layout(params)
        nh = self.n_head
        d = self.hidden_size // nh
        b = state.lengths.shape[0]
        pos = jnp.minimum(state.lengths, self.seq_len - 1)
        x = jnp.take(params["tok_emb"], tokens.astype(jnp.int32),
                     axis=0)[:, None]
        x = x + jnp.take(params["pos_emb"], pos, axis=0)[:, None]
        k_caches, v_caches = [], []
        for i in range(self.n_block):
            p = params[f"block{i}"]
            qkv = jnp.matmul(x, p["qkv_w"].astype(x.dtype)) + \
                p["qkv_b"].astype(x.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            o, kc, vc, _ = cached_attention_step(
                q.reshape(b, 1, nh, d), k.reshape(b, 1, nh, d),
                v.reshape(b, 1, nh, d), state.k_cache[i],
                state.v_cache[i], state.lengths)
            k_caches.append(kc)
            v_caches.append(vc)
            a = jnp.matmul(o.reshape(b, 1, self.hidden_size),
                           p["proj_w"].astype(x.dtype)) + \
                p["proj_b"].astype(x.dtype)
            n = _dp_dropout_add_ln(a, x, p["ln1_g"], p["ln1_b"], None,
                                   0.0, False)
            m = self._ffn(p, n, False)
            x = _dp_dropout_add_ln(m, n, p["ln2_g"], p["ln2_b"], None,
                                   0.0, False)
        state = state._replace(k_cache=tuple(k_caches),
                               v_cache=tuple(v_caches),
                               lengths=state.lengths + 1)
        return self.lm_logits(params, x[:, 0]), state

    def decode_chunk(self, params, state, tokens, n_valid=None):
        """Advance every slot C tokens in ONE rectangular attention step:
        (B, C) ids -> ((B, C, vocab), state).

        The two decode fast paths share this call. Chunked prefill feeds
        prompt slices (C = chunk size; ``n_valid`` (B,) masks a ragged
        final chunk — lengths advance by n_valid and the tail rows land
        above the watermark, never attended, overwritten by the next
        write). Speculative verification feeds [last, draft_1..draft_k]
        (C = k + 1): row i's logits score draft i+1, row k is the bonus
        token, and rejected suffixes roll back by plain ``lengths``
        surgery since their rows also sit above the new watermark.

        Row c embeds at position ``lengths + c`` and attends slab keys
        ``<= lengths + c`` (``cached_attention_chunk``) — the jaxpr still
        carries no (S, S) contraction, so ``decode_step_is_cached``
        holds for any C < S.
        """
        from .....ops.kv_cache import cached_attention_chunk
        self._require_decode_layout(params)
        nh = self.n_head
        d = self.hidden_size // nh
        b, c = tokens.shape
        pos = jnp.minimum(
            state.lengths[:, None] + jnp.arange(c)[None, :],
            self.seq_len - 1)
        x = jnp.take(params["tok_emb"], tokens.astype(jnp.int32), axis=0)
        x = x + jnp.take(params["pos_emb"], pos, axis=0)
        k_caches, v_caches = [], []
        new_lengths = state.lengths
        for i in range(self.n_block):
            p = params[f"block{i}"]
            qkv = jnp.matmul(x, p["qkv_w"].astype(x.dtype)) + \
                p["qkv_b"].astype(x.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            o, kc, vc, new_lengths = cached_attention_chunk(
                q.reshape(b, c, nh, d), k.reshape(b, c, nh, d),
                v.reshape(b, c, nh, d), state.k_cache[i],
                state.v_cache[i], state.lengths, n_valid=n_valid)
            k_caches.append(kc)
            v_caches.append(vc)
            a = jnp.matmul(o.reshape(b, c, self.hidden_size),
                           p["proj_w"].astype(x.dtype)) + \
                p["proj_b"].astype(x.dtype)
            n = _dp_dropout_add_ln(a, x, p["ln1_g"], p["ln1_b"], None,
                                   0.0, False)
            m = self._ffn(p, n, False)
            x = _dp_dropout_add_ln(m, n, p["ln2_g"], p["ln2_b"], None,
                                   0.0, False)
        state = state._replace(k_cache=tuple(k_caches),
                               v_cache=tuple(v_caches),
                               lengths=new_lengths)
        return self.lm_logits(params, x), state

    def call(self, params, inputs, training=False, rng=None, **kw):
        with jax.named_scope("zoo_embed"):
            e, mask_bias = self._embed(params, inputs, rng, training)
            if rng is not None:
                rng, sub = jax.random.split(rng)
                e = _dropout(e, self.hidden_p_drop, sub, training)
        if "blocks" in params:         # GPipe layout (pipeline_parallel>1)
            x = self._call_pp(params, e, mask_bias, rng, training)
            return (x, self._pooler(params, x))
        states = []
        x = e
        for i in range(self.n_block):
            block_rng = None
            if rng is not None:
                rng, block_rng = jax.random.split(rng)
            x = self._block(params[f"block{i}"], x, mask_bias, block_rng,
                            training)
            states.append(x)
        pooled = self._pooler(params, x)
        if self.output_all_block:
            return tuple(states) + (pooled,)
        return (x, pooled)

    def compute_output_shape(self, input_shape):
        first = input_shape[0] if isinstance(input_shape, list) \
            else input_shape
        seq_shape = (first[0], first[1], self.hidden_size)
        pooled = (first[0], self.hidden_size)
        if self.output_all_block:
            return [seq_shape] * self.n_block + [pooled]
        return [seq_shape, pooled]


class BERT(TransformerLayer):
    """BERT encoder (BERT.scala). Inputs: ``[token_ids (B,L),
    position_ids (B,L), segment_ids (B,L), attention_mask (B,1,1,L)]``."""

    gelu_approximate = False  # BERT.scala overrides gelu with the erf form

    def __init__(self, vocab=40990, hidden_size=768, n_block=12, n_head=12,
                 seq_len=512, intermediate_size=3072, hidden_p_drop=0.1,
                 attn_p_drop=0.1, initializer_range=0.02,
                 output_all_block=True, moe_experts=0, moe_top_k=2,
                 input_shape=None, name=None, **kwargs):
        super().__init__(
            n_block=n_block, hidden_p_drop=hidden_p_drop,
            attn_p_drop=attn_p_drop, n_head=n_head,
            initializer_range=initializer_range, bidirectional=True,
            output_all_block=output_all_block,
            intermediate_size=intermediate_size, vocab=vocab,
            seq_len=seq_len, hidden_size=hidden_size,
            moe_experts=moe_experts, moe_top_k=moe_top_k,
            input_shape=input_shape, name=name)

    def _embedding_params(self, rng):
        params = super()._embedding_params(rng)
        r = jax.random.fold_in(rng, 7)
        params["seg_emb"] = _normal(r, (2, self.hidden_size),
                                    self.initializer_range)
        params["emb_ln_g"] = jnp.ones((self.hidden_size,))
        params["emb_ln_b"] = jnp.zeros((self.hidden_size,))
        return params

    def _embed(self, params, inputs, rng, training):
        tokens, positions, segments, mask = inputs
        tokens = tokens.astype(jnp.int32)
        positions = positions.astype(jnp.int32)
        segments = segments.astype(jnp.int32)
        e = jnp.take(params["tok_emb"], tokens, axis=0)
        e = e + jnp.take(params["pos_emb"], positions, axis=0)
        e = e + jnp.take(params["seg_emb"], segments, axis=0)
        e = self._ln(e, params["emb_ln_g"], params["emb_ln_b"], eps=1e-12)
        # extended mask, parity with BERT.scala buildInput:
        # (-mask + 1) * -10000
        mask_bias = (1.0 - mask.astype(jnp.float32)) * -10000.0
        return e, mask_bias

    def compute_output_shape(self, input_shape):
        first = input_shape[0]
        seq_shape = (first[0], first[1], self.hidden_size)
        pooled = (first[0], self.hidden_size)
        if self.output_all_block:
            return [seq_shape] * self.n_block + [pooled]
        return [seq_shape, pooled]
