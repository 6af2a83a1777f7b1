"""Bytes a kernel or a step has to move between HBM and the core, from its
shapes: each operand read once, each result written once. What today's
code moves beyond that (relayout copies, mask bits read from HBM) is the
gap the roofline share shows."""


def flash_train_bytes(cfg: dict, batch: int, seq_len: int,
                      itemsize: int) -> int:
    """Attention kernels of one training step, all blocks. Forward reads
    Q, K, V and writes O and one f32 log-sum-exp per row; backward reads
    Q, K, V, O, dO and the log-sum-exp and writes dQ, dK, dV."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    tensor = batch * seq_len * h * itemsize
    lse = batch * heads * seq_len * 4
    return cfg["num_hidden_layers"] * ((4 * tensor + lse) +
                                       (8 * tensor + lse))


def dln_train_bytes(cfg: dict, batch: int, seq_len: int,
                    itemsize: int) -> int:
    """Fused dropout + add + LayerNorm, two sites a block, one training
    step. Forward reads x and the residual and writes y; backward reads
    dy and the saved normalised input and writes dx and d(residual). The
    mask can be regenerated in the core, so it costs no bytes here."""
    tensor = batch * seq_len * cfg["hidden_size"] * itemsize
    return cfg["num_hidden_layers"] * 2 * (3 * tensor + 4 * tensor)
