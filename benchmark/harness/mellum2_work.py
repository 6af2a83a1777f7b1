"""Operations and bytes a Mellum2 decoder (grouped-query softmax attention
over a sliding window on ``sliding_attention`` layers and over the whole
causal context on ``full_attention`` layers, every block closed by an
expert layer of which this chip holds a share) requires, from its shapes.
``sz`` is what ``references/mellum2.py``'s ``sizes`` makes of the
configuration.

Written from the algorithm, as ``hybrid_decoder_work.py`` is, counting
what the operation requires whatever implements it: a matmul of (m, k) by
(k, n) is 2*m*k*n operations, a backward pass is two more of the same
size, recomputation counts for nothing, elementwise work (the rotation,
the norms) is bytes. Attention counts the (query, key) pairs its mask
keeps, exactly: a causal call of L positions L (L + 1) / 2 a head and
sequence, a window of W keys the band's ``sum_i min(i + 1, W)`` (7,864,832
at 8,192 and 1,024 against 33,558,528 causal), never the blocks a kernel
walks. Routed experts count only the assignments that landed on experts
held here, which the program counts
(``zoo_moe_assignments_held_total``)."""

# an expert is the same gated MLP in every decoder here
from harness.hybrid_decoder_work import (  # noqa: F401
    expert_flops_per_assignment, experts_train_bytes, experts_train_flops)

SLIDING, FULL = "sliding_attention", "full_attention"


def n_layers(sz: dict, kind: str) -> int:
    return sum(k == kind for k in sz["kinds"])


def attention_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs one head of one sequence scores: row i sees
    ``min(i + 1, window)`` keys."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def projection_flops_per_token(sz: dict) -> int:
    """Forward matmuls of one token outside attention scores and the
    routed experts: the four projections of every attention layer, the
    routers, the head."""
    h, d = sz["hidden"], sz["head_dim"]
    qd, kvd = sz["heads"] * d, sz["kv_heads"] * d
    att = 2 * h * qd + 2 * 2 * h * kvd + 2 * qd * h
    return sz["layers"] * (att + 2 * h * sz["router"]) + 2 * h * sz["vocab"]


def _attention_train_flops(sz, batch, seq_len, kind):
    """Forward QK^T and PV over the kept pairs (2 * d each, twice) and a
    backward of twice that, every layer of ``kind``."""
    window = sz["window"] if kind == SLIDING else None
    return 3 * batch * sz["heads"] * n_layers(sz, kind) * \
        attention_pairs(seq_len, window) * 4 * sz["head_dim"]


def window_train_flops(sz: dict, batch: int, seq_len: int) -> int:
    return _attention_train_flops(sz, batch, seq_len, SLIDING)


def full_train_flops(sz: dict, batch: int, seq_len: int) -> int:
    return _attention_train_flops(sz, batch, seq_len, FULL)


def _attention_train_bytes(sz, batch, seq_len, kind, itemsize=2):
    """Attention kernels of one step, every layer of ``kind``. Forward
    reads Q, K, V and writes O and a float32 log-sum-exp a row; backward
    reads Q, K, V, O, dO and the log-sum-exp and writes dQ, dK, dV. K and
    V have ``kv_heads`` heads."""
    q = batch * seq_len * sz["heads"] * sz["head_dim"] * itemsize
    kv = batch * seq_len * sz["kv_heads"] * sz["head_dim"] * itemsize
    lse = batch * sz["heads"] * seq_len * 4
    return n_layers(sz, kind) * ((2 * q + 2 * kv + lse) +
                                 (4 * q + 4 * kv + lse))


def window_train_bytes(sz: dict, batch: int, seq_len: int,
                       itemsize: int = 2) -> int:
    return _attention_train_bytes(sz, batch, seq_len, SLIDING, itemsize)


def full_train_bytes(sz: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> int:
    return _attention_train_bytes(sz, batch, seq_len, FULL, itemsize)


def train_step_flops(sz: dict, batch: int, seq_len: int,
                     held_assignments: int) -> int:
    """Forward plus backward of one optimizer step in which
    ``held_assignments`` assignments (all expert layers together) landed
    on held experts."""
    return 3 * batch * seq_len * projection_flops_per_token(sz) + \
        window_train_flops(sz, batch, seq_len) + \
        full_train_flops(sz, batch, seq_len) + \
        experts_train_flops(sz, held_assignments)
