"""flops.py and bytes.py against hand-worked values for the
configuration, and the training feed's invariants."""

import numpy as np

from harness import bytes as hbytes
from harness import common, flops

CFG = common.load_json("configs", "bert_base_uncased_cls")
BERT = common.sizes(CFG)
train = common.load_module("drivers", "train_classifier")


def test_published_sizes():
    assert BERT["hidden_size"] == 768 and BERT["intermediate_size"] == 3072
    assert (BERT["vocab_size"], BERT["positions"]) == (30522, 512)
    assert CFG["hidden_dropout_prob"] == 0.1 and CFG["reduced"] == []
    assert CFG["attention_probs_dropout_prob"] == 0.1


def test_train_step_flops_by_hand():
    # one block, one 512-token sample, forward: QKV 2*512*768*2304, proj
    # 2*512*768*768, MLP 2*2*512*768*3072, scores and values 4*512*512*768
    block = 1811939328 + 603979776 + 4831838208 + 805306368
    assert flops.block_matmul_flops(BERT, 512) + \
        flops.attention_flops(BERT, 512, 512) == block == 8053063680
    sample = 12 * block + 2 * 768 * 768 + 2 * 768 * 2
    assert flops.classifier_forward_flops(BERT, 512) == sample
    assert flops.train_step_flops(BERT, 32, 512) == 3 * 32 * sample
    assert round(flops.train_step_flops(BERT, 32, 512) / 1e12, 2) == 9.28
    assert flops.flash_train_flops(BERT, 32, 512) == \
        3 * 32 * 12 * 805306368


def test_bytes_by_hand():
    tensor = 32 * 512 * 768 * 2
    assert hbytes.dln_train_bytes(BERT, 32, 512, 2) == 12 * 2 * 7 * tensor
    assert hbytes.flash_train_bytes(BERT, 32, 512, 2) == \
        12 * (12 * tensor + 2 * 32 * 12 * 512 * 4)


def test_every_seed_feeds_rows_that_all_differ():
    """The pool is made from the seed alone; its rows all differ, and the
    label is readable from the tokens."""
    job = dict(common.load_json("traffic", "finetune_b32_l512"),
               pool_batches=3)
    sz = dict(BERT)
    a = train.make_pool(sz, job, 8, np.random.default_rng(2 ** 31 + 5))
    b = train.make_pool(sz, job, 8, np.random.default_rng(2 ** 31 + 5))
    c = train.make_pool(sz, job, 8, np.random.default_rng(6))
    assert all(np.array_equal(x[0][0], y[0][0]) for x, y in zip(a, b))
    assert not np.array_equal(a[0][0][0], c[0][0][0])
    rows = np.concatenate([x[0][0] for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows) == 24
    for (toks, _, _, _), ys in a:
        assert ((toks // 1000 - 1) == ys[:, None]).all()


def test_feed_yields_whole_dispatches_in_the_benchmark_order():
    job = dict(common.load_json("traffic", "finetune_b32_l512"),
               pool_batches=3, seq_len=8)
    pool = train.make_pool(dict(BERT), job, 4, np.random.default_rng(1))
    feed = train.make_feed(pool, 4, group=2, n_groups=2)
    got = list(feed.batches(4, shuffle=True, seed=9))
    assert len(got) == 4
    for i, mb in enumerate(got):
        assert np.array_equal(mb[1], pool[i % 3][1])
