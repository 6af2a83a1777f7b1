"""One route per shape under ``ops/``, decided in ``ops/_route.py``.

- the decision "kernel or XLA" for the three ops that have a kernel, read
  off each op's public entry: what the traced call holds;
- the kernels' and the scan's block sizes as a function of the shape, and
  of nothing in the environment;
- under ``ops/`` the environment is read in one module, for three names;
- every ``ZOO_*`` name the documents give is one the program reads.
"""

import ast
import dataclasses
import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from analytics_zoo_tpu.common.nncontext import ZooConfig
from analytics_zoo_tpu.ops import _route as R
from analytics_zoo_tpu.ops import attention as A
from analytics_zoo_tpu.ops.delta_rule import chunk_gated_delta_rule
from analytics_zoo_tpu.ops.fused_dropout_ln import dropout_add_layer_norm
from analytics_zoo_tpu.ops.kv_cache import _iter_eqns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
THE_THREE = ("ZOO_TPU_DISABLE_PALLAS", "ZOO_TPU_FORCE_PALLAS",
             "ZOO_TPU_PALLAS_INTERPRET")
# what chose a duplicate path or overrode a shape's own value before PR 30
DELETED = {"ZOO_TPU_FLASH_REMAT": "full", "ZOO_TPU_FLASH_BWD": "xla",
           "ZOO_TPU_ATTN_FALLBACK": "reference", "ZOO_TPU_ATTN_REMAT": "1",
           "ZOO_TPU_ATTN_BLOCK_Q": "128", "ZOO_TPU_ATTN_BLOCK_K": "128",
           "ZOO_TPU_ATTN_FALLBACK_BLOCK_Q": "128",
           "ZOO_TPU_ATTN_FALLBACK_BLOCK_K": "128",
           "ZOO_TPU_KERNEL_MIN_SEQ": "4096",
           "ZOO_TPU_DISABLE_FUSED_DLN": "1"}


def _shape(*dims):
    return jax.ShapeDtypeStruct(dims, jnp.float32)


def _attention(length, d=64, dv=None):
    x = _shape(1, 2, length, d)
    return (lambda q, k, v: A.flash_attention(q, k, v, causal=True)), \
        (x, x, _shape(1, 2, length, dv or d))


def _dln(rows, d=128):
    x, w = _shape(rows, d), _shape(d)
    return (lambda x, r, g, b: dropout_add_layer_norm(
        x, r, g, b, jax.random.key(0), 0.1)), (x, x, w, w)


def _delta_rule(length, chunk=128, per_channel=False):
    qk, gb = _shape(1, length, 2, 128), _shape(1, length, 2)
    return (lambda q, k, v, g, b: chunk_gated_delta_rule(
        q, k, v, g, b, chunk)), (qk, qk, qk, qk if per_channel else gb, gb)


OPS = {"attention": _attention, "dln": _dln, "delta_rule": _delta_rule,
       # the shapes Kimi Linear brought: a decay per key channel, and
       # values narrower than keys
       "delta_rule_per_channel": functools.partial(_delta_rule,
                                                   per_channel=True),
       "latent_attention": functools.partial(_attention, d=192, dv=128)}


def _kernels(fn, args):
    """The ``name=`` of every Pallas kernel the traced call holds."""
    return sorted(e.params["name"] for e in _iter_eqns(
        jax.make_jaxpr(fn)(*args).jaxpr) if e.primitive.name == "pallas_call")


@pytest.mark.parametrize("env,kernel", [
    ((), False),                                  # the CPU: XLA
    (("ZOO_TPU_PALLAS_INTERPRET",), True),
    (("ZOO_TPU_PALLAS_INTERPRET", "ZOO_TPU_DISABLE_PALLAS"), False),
], ids=["cpu", "interpret", "interpret+disable"])
@pytest.mark.parametrize("op", sorted(OPS))
def test_kernel_or_xla_is_one_decision(monkeypatch, op, env, kernel):
    for name in THE_THREE:
        monkeypatch.delenv(name, raising=False)
    for name in env:
        monkeypatch.setenv(name, "1")
    found = _kernels(*OPS[op](512))
    assert bool(found) == kernel, found
    if kernel:
        assert found == {"attention": ["zoo_flash_fwd"],
                         "latent_attention": ["zoo_flash_fwd"],
                         "dln": ["zoo_dln_fwd"],
                         "delta_rule": ["zoo_gdn_local_fwd",
                                        "zoo_gdn_scan_fwd"],
                         "delta_rule_per_channel": [
                             "zoo_kda_local_fwd", "zoo_kda_scan_fwd"]}[op]


@pytest.mark.parametrize("op,build,why", [
    ("attention", lambda: _attention(8192, d=96),
     "attention at length 8192 has no kernel route: head size 96"),
    ("delta_rule", lambda: _delta_rule(8192, chunk=64),
     "the delta rule at length 8192 has no kernel route: chunk 64"),
    ("dln", lambda: _dln(8192, d=96), None),
    ("delta_rule_per_channel",
     lambda: _delta_rule(8192, chunk=64, per_channel=True),
     "the delta rule at length 8192 has no kernel route: chunk 64"),
    ("latent_attention", lambda: _attention(8192, d=192, dv=96),
     "attention at length 8192 has no kernel route: value head size 96"),
])
def test_a_long_call_without_a_kernel_is_loud_on_the_chip(monkeypatch, op,
                                                          build, why):
    """From 8,192 on a TPU backend, a call the rules refuse raises and
    names the op and the rule; DLN has no length and never raises; short
    of 8,192, and with ``ZOO_TPU_DISABLE_PALLAS=1``, the carrier runs."""
    for name in THE_THREE:
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(R, "mosaic_partition_ok", lambda: True)
    if why is None:
        assert _kernels(*build()) == []
        return
    with pytest.raises(ValueError, match=why):
        _kernels(*build())
    monkeypatch.setenv("ZOO_TPU_DISABLE_PALLAS", "1")
    assert _kernels(*build()) == []


BLOCKS = [
    # (lq, lk, head size) -> (block_q, block_k); the two cells' shapes first
    ((512, 512, 64), (512, 512)),             # bert_train_l512
    ((8192, 8192, 256), (512, 512)),          # qwen3next_pretrain_l8192
    ((8192, 8192, 192), (512, 512)),          # kimilinear_pretrain_l8192
    ((8192, 8192, 64), (512, 1024)),
    ((640, 640, 64), (128, 128)),             # only 128 divides it
    ((128, 2048, None), (128, 1024)),
]
FALLBACK = [(512, 256), (384, 128), (333, 333), (256, 128), (128, 128)]


@pytest.fixture(params=["clean", "deleted-names-set"])
def environment(request, monkeypatch):
    for name, value in DELETED.items():
        if request.param == "clean":
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, value)


@pytest.mark.parametrize("shape,blocks", BLOCKS)
def test_kernel_blocks_are_a_function_of_the_shape(environment, shape,
                                                   blocks):
    lq, lk, d = shape
    assert A._resolve_blocks(lq, lk, None, None, d) == blocks
    # an explicit block is taken when it divides the length, else ignored
    assert A._resolve_blocks(lq, lk, 128, 128, d) == (128, 128)
    assert A._resolve_blocks(lq, lk, 96, 96, d) == blocks


@pytest.mark.parametrize("length,block", FALLBACK)
def test_scan_blocks_are_a_function_of_the_length(environment, length,
                                                  block):
    assert A._fallback_block(length) == block
    assert block == length or (block < length and length % block == 0)


def test_the_deleted_switches_are_names_nothing_knows(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FLASH_REMAT", "full-residual")
    assert A.KERNEL_MIN_SEQ == 512
    assert not hasattr(A, "_flash_remat_policy")
    assert not hasattr(ZooConfig.from_env(), "flash_remat")


def _environment_reads(tree):
    """Lines of a module that touch ``os.environ`` or ``os.getenv``,
    however they were imported."""
    lines = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in ("environ",
                                                             "getenv"):
            lines.append(node.lineno)
        elif isinstance(node, ast.ImportFrom) and node.module == "os" and \
                {a.name for a in node.names} & {"environ", "getenv"}:
            lines.append(node.lineno)
    return lines


def test_ops_reads_the_environment_in_one_module_for_three_names():
    reads, named = {}, {}
    for path in sorted(glob.glob(os.path.join(
            REPO, "analytics_zoo_tpu", "ops", "*.py"))):
        source = open(path).read()
        reads[os.path.basename(path)] = _environment_reads(ast.parse(source))
        named[os.path.basename(path)] = set(
            re.findall(r"ZOO_[A-Z0-9_]+", source))
    assert [f for f, lines in reads.items() if lines] == ["_route.py"], reads
    assert named["_route.py"] == set(THE_THREE)
    # elsewhere under ops/ a docstring may name one of the three, no other
    assert set().union(*named.values()) == set(THE_THREE), named


DOCUMENTS = sorted(
    os.path.relpath(p, REPO) for p in
    [os.path.join(REPO, "README.md")] +
    glob.glob(os.path.join(REPO, "docs", "*.md"))
    if re.search(r"ZOO_[A-Z_]+", open(p).read()))


@functools.lru_cache(maxsize=None)
def _names_the_program_reads():
    text = []
    for root in ("analytics_zoo_tpu", "scripts"):
        for folder, _, files in os.walk(os.path.join(REPO, root)):
            text += [open(os.path.join(folder, f), errors="ignore").read()
                     for f in files if not f.endswith(".pyc")]
    text.append(open(os.path.join(REPO, "tests", "conftest.py")).read())
    names = set(re.findall(r"ZOO_[A-Z0-9_]+", "\n".join(text)))
    # ZooConfig.from_env reads ZOO_TPU_<FIELD> for every field
    return names | {"ZOO_TPU_" + f.name.upper()
                    for f in dataclasses.fields(ZooConfig)}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_only_what_the_program_reads(document):
    given = set(re.findall(r"ZOO_[A-Z0-9_]+",
                           open(os.path.join(REPO, document)).read()))
    # a family written with a wildcard (ZOO_TPU_*) ends in an underscore
    given = {n for n in given if not n.endswith("_")}
    unread = sorted(given - _names_the_program_reads())
    assert not unread, f"{document} gives names nothing reads: {unread}"
