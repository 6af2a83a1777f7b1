"""Operations and bytes a Kimi Linear decoder (Kimi Delta Attention and
latent-attention blocks, a dense gated MLP in the leading blocks and an
expert layer of which this chip holds a share in the others) requires,
from its shapes. ``sz`` is what ``references/kimi_linear.py``'s ``sizes``
makes of the configuration.

Written from the algorithm, as ``hybrid_decoder_work.py`` is, and counting
what the operation requires whatever implements it: a matmul of (m, k) by
(k, n) is 2*m*k*n operations, a backward pass is two more of the same
size, recomputation counts for nothing, elementwise work is bytes. Causal
attention is half a square, at the keys' width for the scores and the
values' for the output. The delta rule's bytes are its operands and their
cotangents once each: the state makes no round trip that the algorithm
asks for. Routed experts count only the assignments that landed on
experts held here, which the program counts
(``zoo_moe_assignments_held_total``)."""

# an expert is the same gated MLP in both decoders
from harness.hybrid_decoder_work import (  # noqa: F401
    expert_flops_per_assignment, experts_train_flops)

KDA_CHUNK = 128      # ops/delta_rule.py DEFAULT_CHUNK


def n_attention(sz: dict) -> int:
    return len(sz["mla_layers"])


def n_kda(sz: dict) -> int:
    return len(sz["kda_layers"])


def n_dense(sz: dict) -> int:
    return min(sz["dense_layers"], sz["layers"])


def projection_flops_per_token(sz: dict) -> int:
    """Forward matmuls of one token outside attention scores, the delta
    rule and the routed experts: both mixers' projections, the dense MLP,
    the router, the shared expert, the head."""
    h = sz["hidden"]
    n, d = sz["kda_heads"], sz["kda_dim"]
    kda = 2 * h * 3 * n * d + 2 * h * n + 2 * 2 * (h * d + d * n * d) + \
        2 * n * d * h
    heads = sz["heads"]
    mla = 2 * h * heads * (sz["nope"] + sz["rope"]) + \
        2 * h * (sz["kv_rank"] + sz["rope"]) + \
        2 * sz["kv_rank"] * heads * (sz["nope"] + sz["v_dim"]) + \
        2 * heads * sz["v_dim"] * h
    moe = 2 * h * sz["router"] + 6 * h * sz["shared_width"]
    dense = 6 * h * sz["dense_width"]
    return n_kda(sz) * kda + n_attention(sz) * mla + \
        n_dense(sz) * dense + (sz["layers"] - n_dense(sz)) * moe + \
        2 * h * sz["vocab"]


def causal_attention_flops(sz: dict, seq_len: int) -> int:
    """Forward QK^T and PV of one sequence in one latent-attention block,
    the lower triangle only: half of 2 * L * L * heads * (key width +
    value width)."""
    return seq_len * seq_len * sz["heads"] * (
        sz["nope"] + sz["rope"] + sz["v_dim"])


def kda_chunk_flops(sz: dict, seq_len: int, chunk: int = KDA_CHUNK) -> int:
    """Forward operations of the chunked delta rule for one sequence in
    one block: per head and chunk the two C x C score tiles, the
    unit-triangular solve against (dk + dv) columns, the tile applied to
    the written values, and three products with the state. The decay a
    channel is elementwise work inside those and counts as bytes."""
    c, d = chunk, sz["kda_dim"]
    per_chunk = 2 * (2 * c * c * d) + c * c * (d + d) + 2 * c * c * d + \
        3 * (2 * c * d * d)
    return sz["kda_heads"] * -(-seq_len // c) * per_chunk


def mla_train_flops(sz: dict, batch: int, seq_len: int) -> int:
    """Attention kernels of one training step, all latent-attention
    blocks: forward 2 matmuls per head, backward 4."""
    return 3 * batch * n_attention(sz) * causal_attention_flops(sz, seq_len)


def kda_train_flops(sz: dict, batch: int, seq_len: int) -> int:
    return 3 * batch * n_kda(sz) * kda_chunk_flops(sz, seq_len)


def train_step_flops(sz: dict, batch: int, seq_len: int,
                     held_assignments: int) -> int:
    """Forward plus backward of one optimizer step in which
    ``held_assignments`` assignments (all blocks together) landed on held
    experts."""
    return 3 * batch * seq_len * projection_flops_per_token(sz) + \
        mla_train_flops(sz, batch, seq_len) + \
        kda_train_flops(sz, batch, seq_len) + \
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


def kda_train_bytes(sz: dict, batch: int, seq_len: int,
                    itemsize: int = 2) -> int:
    """The delta rule of one step, all KDA blocks: q, k, v, o in the
    compute dtype, beta and the log decay a key channel in float32, read or
    written once; their cotangents once more. No state traffic."""
    rows = batch * seq_len * sz["kda_heads"]
    once = rows * 4 * sz["kda_dim"] * itemsize + rows * 4 + \
        rows * sz["kda_dim"] * 4
    return n_kda(sz) * 2 * once


def experts_train_bytes(sz: dict, held_assignments: int, steps: int,
                        itemsize: int = 2) -> int:
    """The held experts over ``steps`` steps: every held expert's three
    matrices read forward, read backward and their gradient written, each
    once an expert layer and step; per assignment the token's row read and
    the result's written forward, and x, dy read and dx written
    backward."""
    weights = sz["held"] * 3 * sz["hidden"] * sz["expert_width"] * itemsize
    row = sz["hidden"] * itemsize
    return steps * (sz["layers"] - n_dense(sz)) * 3 * weights + \
        held_assignments * 5 * row
