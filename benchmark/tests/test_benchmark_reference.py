"""The plain float32 reference against the program at a small size on the
CPU (both sides at "highest" matmul precision; under dropout, as two
draws of one distribution), and the lower-precision control and the
planted faults, which have to read further from the reference than the
program does."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import common

ref = common.load_module("references", "transformer")
train = common.load_module("drivers", "train_classifier")

SZ = {"hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 2,
      "num_attention_heads": 2, "vocab_size": 120, "positions": 24,
      "num_labels": 2}


def test_seed_key_takes_seeds_past_32_bits():
    a = ref.init_params(SZ, ref.seed_key(3))
    b = ref.init_params(SZ, ref.seed_key(3 + 2 ** 31))
    assert not np.allclose(a["tok_emb"], b["tok_emb"])
    c = ref.init_params(SZ, ref.seed_key(3))
    np.testing.assert_array_equal(a["tok_emb"], c["tok_emb"])


def test_bert_reference_matches_the_program():
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        BERT

    w = ref.init_params(SZ, ref.seed_key(1))
    rng = np.random.default_rng(0)
    b, l = 3, 16
    toks = rng.integers(0, SZ["vocab_size"], (b, l)).astype(np.int32)
    poss = np.tile(np.arange(l, dtype=np.int32), (b, 1))
    segs = rng.integers(0, 2, (b, l)).astype(np.int32)
    mask = np.ones((b, l), np.float32)
    mask[1, 12:] = 0.0
    labels = np.array([0, 1, 1], np.int32)
    layer = BERT(vocab=SZ["vocab_size"], hidden_size=32, n_block=2, n_head=2,
                 seq_len=24, intermediate_size=64, hidden_p_drop=0.0,
                 attn_p_drop=0.0, output_all_block=False)
    tree = train.to_program_tree(w, "bert", "classifier")
    with jax.default_matmul_precision("highest"):
        _, pooled = layer.call(tree["bert"], [toks, poss, segs,
                                              mask[:, None, None, :]])
        theirs = jax.nn.softmax(pooled @ tree["classifier"]["kernel"] +
                                tree["classifier"]["bias"], -1)
    ours = ref.bert_probs(w, toks, poss, segs, mask, SZ)
    np.testing.assert_allclose(ours, theirs, atol=2e-5)
    loss = ref.bert_loss(w, (toks, poss, segs, mask, labels), SZ)
    want = -np.log(np.asarray(theirs)[np.arange(b), labels]).sum()
    assert float(loss) == pytest.approx(float(want), rel=1e-4)


def test_dropout_is_the_programs_in_distribution():
    """Reference and program in training mode at p = 0.1, 300 draws of
    the masks each: the mean and the spread of the summed loss agree to
    what 300 draws can tell. (On the CPU the program draws bernoulli
    masks at the same four sites.)"""
    from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import \
        BERT

    w = ref.init_params(SZ, ref.seed_key(8), std=0.3)
    rng = np.random.default_rng(3)
    b, l, n = 4, 16, 300
    toks = rng.integers(0, SZ["vocab_size"], (b, l)).astype(np.int32)
    poss = np.tile(np.arange(l, dtype=np.int32), (b, 1))
    segs = np.zeros((b, l), np.int32)
    mask = np.ones((b, l), np.float32)
    labels = np.array([0, 1, 1, 0], np.int32)
    layer = BERT(vocab=SZ["vocab_size"], hidden_size=32, n_block=2, n_head=2,
                 seq_len=24, intermediate_size=64, hidden_p_drop=0.1,
                 attn_p_drop=0.1, output_all_block=False)
    tree = train.to_program_tree(w, "bert", "classifier")
    drop = {"hidden": 0.1, "attention": 0.1}

    def theirs(key):
        _, pooled = layer.call(tree["bert"], [toks, poss, segs,
                                              mask[:, None, None, :]],
                               training=True, rng=key)
        p = jax.nn.softmax(pooled @ tree["classifier"]["kernel"] +
                           tree["classifier"]["bias"], -1)
        return -jnp.log(p[jnp.arange(b), labels]).sum()

    def ours(key):
        return ref.bert_loss(w, (toks, poss, segs, mask, labels), SZ,
                             drop=drop, key=key)

    keys = jax.random.split(jax.random.PRNGKey(0), n)
    with jax.default_matmul_precision("highest"):
        a = np.asarray(jax.jit(jax.vmap(theirs))(keys))
    c = np.asarray(jax.jit(jax.vmap(ours))(keys))
    plain = float(ref.bert_loss(w, (toks, poss, segs, mask, labels), SZ))
    assert a.std() > 0.02 and abs(a.mean() - plain) < 10 * a.std()
    se = np.sqrt((a.var() + c.var()) / n)
    assert abs(a.mean() - c.mean()) < 4 * se
    assert 0.75 < a.std() / c.std() < 1.33
    # no key: the identity, whatever the rates
    assert float(ref.bert_loss(w, (toks, poss, segs, mask, labels), SZ,
                               drop=drop)) == plain


def _train_case(precision, rows_kept=None):
    w = ref.init_params(SZ, ref.seed_key(4))
    rng = np.random.default_rng(4)
    k, nblk, rows, l = 2, 2, 2, 16
    n = rows_kept or rows
    batches = (rng.integers(0, 120, (k, nblk, rows, l)).astype(np.int32),
               np.tile(np.arange(l, dtype=np.int32), (k, nblk, rows, 1)),
               np.zeros((k, nblk, rows, l), np.int32),
               np.ones((k, nblk, rows, l), np.float32),
               rng.integers(0, 2, (k, nblk, rows)).astype(np.int32))
    batches = tuple(a[:, :, :n] for a in batches)
    losses, g1, mu, nu, params = jax.jit(lambda p, b: ref.train_steps(
        p, b, SZ, 1e-3, precision=precision))(w, batches)
    flat = lambda t: {jax.tree_util.keystr(p): np.asarray(v) for p, v in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    norm = lambda t: {k: float(np.linalg.norm(v)) for k, v in
                      flat(t).items()}
    return {"loss": float(losses[-1]), "losses": [float(x) for x in losses],
            "g1": {k: float(np.linalg.norm(v)) for k, v in flat(g1).items()},
            "mu": norm(mu),
            "rms": {k: float(np.sqrt(v.sum())) for k, v in flat(nu).items()},
            "delta": norm(jax.tree.map(jnp.subtract, params, w))}


def test_reference_adam_is_optax_adam():
    import optax

    w = ref.init_params(SZ, ref.seed_key(4))
    got = _train_case("f32")
    tx = optax.adam(1e-3)
    state = tx.init(w)
    assert got["losses"][0] > 0 and len(got["losses"]) == 2
    # one hand-rolled step against optax on the same gradient
    g = jax.tree.map(jnp.ones_like, w)
    upd, _ = tx.update(g, state, w)
    assert float(jnp.abs(upd["tok_emb"] + 1e-3).max()) < 1e-6


@pytest.mark.parametrize("side,kw,want", [
    ("program_bf16", dict(precision="bf16"), True),
    ("control_fp8", dict(precision="fp8"), False),
    ("fault_half_batch", dict(precision="f32", rows_kept=1), False)])
def test_control_and_fault_are_not_correct(side, kw, want):
    """The reference in the configuration's own precision, the fp8 control
    and the half-batch fault, each put in the program's place and judged
    as a run is: ``compare`` under limits and ``run.decide``. (The cell's
    limits are set from chip readings at the cell's size, PERF.md; these
    are the rehearsal cell's.)"""
    import run

    limits = {"change_gap": 0.2, "gradient_gap": 0.2}
    checks, _ = train.compare(_train_case(**kw), _train_case("f32"), limits)
    assert run.decide(checks, 0) is want, checks
