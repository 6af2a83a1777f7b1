"""Operations and bytes a JoyAI-LLM-Flash decoder (latent attention with a
query rank and rotary positions in every block, a dense gated MLP in the
leading blocks and an expert layer of which this chip holds a share in the
others, and one multi-token-prediction module behind the stack that shares
the embedding and the head) requires, from its shapes. ``sz`` is what
``references/joyai_llm_flash.py``'s ``sizes`` makes of the configuration.

Written from the algorithm, as ``kimi_linear_work.py`` is, and counting
what the operation requires whatever implements it: a matmul of (m, k) by
(k, n) is 2*m*k*n operations, a backward pass is two more of the same
size, recomputation counts for nothing, elementwise work (the rotation,
the norms, the bias rule) is bytes. Causal attention is half a square, at
the keys' width for the scores and the values' for the output. The module
is one more attention block and one more expert layer, the joining
projection ``W_eh`` and a second pass of the head. Routed experts count
only the assignments that landed on experts held here, which the program
counts (``zoo_moe_assignments_held_total``, the module's among them)."""

# an expert is the same gated MLP in every decoder here
from harness.hybrid_decoder_work import (  # noqa: F401
    expert_flops_per_assignment, experts_train_flops)


def n_dense(sz: dict) -> int:
    return min(sz["dense_layers"], sz["layers"])


def n_attention(sz: dict) -> int:
    """Latent-attention blocks: every block of the stack and the
    module's."""
    return sz["layers"] + 1


def n_expert_layers(sz: dict) -> int:
    """Expert layers: the stack's blocks past the dense ones and the
    module's."""
    return sz["layers"] - n_dense(sz) + 1


def projection_flops_per_token(sz: dict) -> int:
    """Forward matmuls of one token outside attention scores and the
    routed experts: the five projections of every mixer, the dense MLP,
    the routers, the shared experts, ``W_eh`` and the head twice."""
    h, heads = sz["hidden"], sz["heads"]
    mla = 2 * h * sz["q_rank"] + \
        2 * sz["q_rank"] * heads * (sz["nope"] + sz["rope"]) + \
        2 * h * (sz["kv_rank"] + sz["rope"]) + \
        2 * sz["kv_rank"] * heads * (sz["nope"] + sz["v_dim"]) + \
        2 * heads * sz["v_dim"] * h
    moe = 2 * h * sz["router"] + 6 * h * sz["shared_width"]
    dense = 6 * h * sz["dense_width"]
    return n_attention(sz) * mla + n_dense(sz) * dense + \
        n_expert_layers(sz) * moe + 2 * (2 * h) * h + 2 * (2 * h * sz["vocab"])


def causal_attention_flops(sz: dict, seq_len: int) -> int:
    """Forward QK^T and PV of one sequence in one latent-attention block,
    the lower triangle only: half of 2 * L * L * heads * (key width +
    value width)."""
    return seq_len * seq_len * sz["heads"] * (
        sz["nope"] + sz["rope"] + sz["v_dim"])


def mla_train_flops(sz: dict, batch: int, seq_len: int) -> int:
    """Attention kernels of one training step, all latent-attention
    blocks: forward 2 matmuls per head, backward 4."""
    return 3 * batch * n_attention(sz) * causal_attention_flops(sz, seq_len)


def train_step_flops(sz: dict, batch: int, seq_len: int,
                     held_assignments: int) -> int:
    """Forward plus backward of one optimizer step in which
    ``held_assignments`` assignments (all expert layers together) landed
    on held experts."""
    return 3 * batch * seq_len * projection_flops_per_token(sz) + \
        mla_train_flops(sz, batch, seq_len) + \
        experts_train_flops(sz, held_assignments)


def mla_train_bytes(sz: dict, batch: int, seq_len: int,
                    itemsize: int = 2) -> int:
    """Attention kernels of one step, all latent-attention blocks. Forward
    reads Q, K (keys' width) and V, writes O (values' width) and a float32
    log-sum-exp a row; backward reads Q, K, V, O, dO and the log-sum-exp
    and writes dQ, dK, dV."""
    rows = batch * seq_len * sz["heads"] * itemsize
    qk, v = rows * (sz["nope"] + sz["rope"]), rows * sz["v_dim"]
    lse = batch * sz["heads"] * seq_len * 4
    return n_attention(sz) * ((2 * qk + 2 * v + lse) +
                              (4 * qk + 4 * v + lse))


def experts_train_bytes(sz: dict, held_assignments: int, steps: int,
                        itemsize: int = 2) -> int:
    """The held experts over ``steps`` steps: every held expert's three
    matrices read forward, read backward and their gradient written, each
    once an expert layer and step; per assignment the token's row read and
    the result's written forward, and x, dy read and dx written
    backward."""
    weights = sz["held"] * 3 * sz["hidden"] * sz["expert_width"] * itemsize
    row = sz["hidden"] * itemsize
    return steps * n_expert_layers(sz) * 3 * weights + \
        held_assignments * 5 * row
