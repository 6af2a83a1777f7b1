"""Operations a transformer configuration requires, from its shapes.

Written from the algorithm: a matmul of (m, k) by (k, n) is 2*m*k*n
operations, the backward pass of a matmul is two more of the same size,
recomputation counts for nothing. Elementwise work (LayerNorm, GELU,
softmax, Adam) is left out: it is bytes, not operations, on this chip."""


def block_matmul_flops(cfg: dict, tokens: int) -> int:
    """Forward matmuls of one block outside attention, for ``tokens`` rows:
    fused QKV, the output projection, the two MLP matmuls."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    return 2 * tokens * (h * 3 * h + h * h + 2 * h * m)


def attention_flops(cfg: dict, q_len: int, kv_len: int) -> int:
    """Forward QK^T and PV of one block for one sequence: 2 matmuls of
    (q_len, d) x (d, kv_len) per head."""
    return 4 * q_len * kv_len * cfg["hidden_size"]


def classifier_forward_flops(cfg: dict, seq_len: int) -> int:
    """One sample through a BERT-style classifier: blocks, pooler, head."""
    h = cfg["hidden_size"]
    per_block = block_matmul_flops(cfg, seq_len) + \
        attention_flops(cfg, seq_len, seq_len)
    return cfg["num_hidden_layers"] * per_block + 2 * h * h + \
        2 * h * cfg["num_labels"]


def train_step_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """Forward plus backward (twice the forward) of one optimizer step."""
    return 3 * batch * classifier_forward_flops(cfg, seq_len)


def flash_train_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """What the attention kernels of one training step must compute, all
    blocks: forward 2 matmuls per head, backward 4 (dV, dP, dQ, dK); the
    score recompute a flash backward chooses to do is not counted."""
    return 3 * batch * cfg["num_hidden_layers"] * \
        attention_flops(cfg, seq_len, seq_len)
