"""Operations and bytes a hybrid decoder (Gated DeltaNet and gated
softmax-attention blocks, each closed by an expert layer of which this
chip holds a share) requires, from its shapes. ``sz`` is what the
reference's ``sizes`` makes of the configuration.

Written from the algorithm, as ``flops.py`` is: a matmul of (m, k) by
(k, n) is 2*m*k*n operations, a backward pass is two more of the same
size, recomputation counts for nothing, elementwise work is bytes. Causal
attention is half a square. Routed experts count only the assignments
that landed on experts held here, which the program counts
(``zoo_moe_assignments_held_total``)."""

GDN_CHUNK = 128      # ops/delta_rule.py DEFAULT_CHUNK


def n_attention(sz: dict) -> int:
    return sum((i + 1) % sz["interval"] == 0 for i in range(sz["layers"]))


def projection_flops_per_token(sz: dict) -> int:
    """Forward matmuls of one token outside attention scores, the delta
    rule and the routed experts: both mixers' projections, the router, the
    shared expert and its gate, the head."""
    h = sz["hidden"]
    kd, vd = sz["nk"] * sz["dk"], sz["nv"] * sz["dv"]
    qd, kvd = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]
    gdn = 2 * h * (2 * kd + 2 * vd) + 2 * h * 2 * sz["nv"] + 2 * vd * h
    att = 2 * h * 2 * qd + 2 * 2 * h * kvd + 2 * qd * h
    moe = 2 * h * sz["router"] + 6 * h * sz["shared_width"] + 2 * h
    n_att = n_attention(sz)
    return (sz["layers"] - n_att) * gdn + n_att * att + \
        sz["layers"] * moe + 2 * h * sz["vocab"]


def causal_attention_flops(sz: dict, seq_len: int) -> int:
    """Forward QK^T and PV of one sequence in one attention block, the
    lower triangle only: half of 4 * L * L * heads * d."""
    return 2 * seq_len * seq_len * sz["heads"] * sz["head_dim"]


def gdn_chunk_flops(sz: dict, seq_len: int, chunk: int = GDN_CHUNK) -> int:
    """Forward operations of the chunked delta rule for one sequence in
    one block: per head and chunk the two C x C score tiles (K K^T, Q
    K^T), the unit-triangular solve against (dk + dv) columns, the tile
    applied to the written values, and three products with the state."""
    c, dk, dv = chunk, sz["dk"], sz["dv"]
    per_chunk = 2 * (2 * c * c * dk) + c * c * (dk + dv) + 2 * c * c * dv + \
        3 * (2 * c * dk * dv)
    return sz["nv"] * -(-seq_len // c) * per_chunk


def expert_flops_per_assignment(sz: dict) -> int:
    """Forward: gate, up and down projections of one token in one expert."""
    return 6 * sz["hidden"] * sz["expert_width"]


def flash_train_flops(sz: dict, batch: int, seq_len: int) -> int:
    """Attention kernels of one training step, all attention blocks:
    forward 2 matmuls per head, backward 4."""
    return 3 * batch * n_attention(sz) * causal_attention_flops(sz, seq_len)


def gdn_train_flops(sz: dict, batch: int, seq_len: int) -> int:
    return 3 * batch * (sz["layers"] - n_attention(sz)) * \
        gdn_chunk_flops(sz, seq_len)


def experts_train_flops(sz: dict, held_assignments: int) -> int:
    return 3 * held_assignments * expert_flops_per_assignment(sz)


def train_step_flops(sz: dict, batch: int, seq_len: int,
                     held_assignments: int) -> int:
    """Forward plus backward of one optimizer step in which
    ``held_assignments`` assignments (all blocks together) landed on held
    experts."""
    return 3 * batch * seq_len * projection_flops_per_token(sz) + \
        flash_train_flops(sz, batch, seq_len) + \
        gdn_train_flops(sz, batch, seq_len) + \
        experts_train_flops(sz, held_assignments)


def flash_train_bytes(sz: dict, batch: int, seq_len: int,
                      itemsize: int = 2) -> int:
    """Attention kernels of one step, all attention blocks. Forward reads
    Q, K, V and writes O and a float32 log-sum-exp a row; backward reads
    Q, K, V, O, dO and the log-sum-exp and writes dQ, dK, dV. K and V have
    ``kv_heads`` heads."""
    q = batch * seq_len * sz["heads"] * sz["head_dim"] * itemsize
    kv = batch * seq_len * sz["kv_heads"] * sz["head_dim"] * itemsize
    lse = batch * sz["heads"] * seq_len * 4
    return n_attention(sz) * ((2 * q + 2 * kv + lse) +
                              (4 * q + 4 * kv + lse))


def gdn_train_bytes(sz: dict, batch: int, seq_len: int,
                    itemsize: int = 2, chunk: int = GDN_CHUNK) -> int:
    """The chunked delta rule of one step, all DeltaNet blocks. Forward
    reads q, k, v (at the value heads' count), the two gates in float32,
    writes o, and reads and writes the float32 state once a chunk;
    backward moves each of those once more and writes the gradients of q,
    k, v and the gates."""
    rows = batch * seq_len * sz["nv"]
    qkvo = rows * (2 * sz["dk"] + 2 * sz["dv"]) * itemsize
    gates = rows * 2 * 4
    state = batch * sz["nv"] * -(-seq_len // chunk) * \
        sz["dk"] * sz["dv"] * 4 * 2
    fwd = qkvo + gates + state
    return (sz["layers"] - n_attention(sz)) * (fwd + 2 * fwd)


def experts_train_bytes(sz: dict, held_assignments: int, steps: int,
                        itemsize: int = 2) -> int:
    """The held experts over ``steps`` steps: every held expert's three
    matrices read forward, read backward and their gradient written, each
    once a block and step; per assignment the token's row read and the
    result's written forward, and x, dy read and dx written backward."""
    weights = sz["held"] * 3 * sz["hidden"] * sz["expert_width"] * itemsize
    row = sz["hidden"] * itemsize
    return steps * sz["layers"] * 3 * weights + held_assignments * 5 * row
