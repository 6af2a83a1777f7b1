"""``readers/unscoped_share.py`` on a synthetic program text and window:
an op the join tags, an op with an ``op_name`` and no tag (the row loop's
sum of a block's gradients over rows among them), an op with no
``op_name``, and a kernel tagged by its own text alone; a result with no
join reads nothing, as on a parent without the scopes."""

import re
import types

import pytest

from conftest import BENCH
from harness import common, hlo_scopes

HLO = '''
HloModule jit_multi_fn
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %multiply.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(multi_fn)/while/body/zoo_mixer_proj/mul"}
}
ENTRY %main {
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(multi_fn)/while/body/transpose(jvp())/while/body/closed_call/zoo_mixer_proj/dot_general"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(multi_fn)/while/body/transpose(jvp())/while/body/add_any"}
  %copy.4 = f32[8]{0} copy(%a), metadata={op_name="jit(multi_fn)/while/body/add"}
  %copy-done.5 = f32[8]{0} copy-done(%c)
  ROOT %convolution.9 = bf16[8]{0} convolution(%a, %b), metadata={op_name="jit(multi_fn)/while/body/zoo_optimizer/mul"}
}
'''
KERNEL = '%c.1 = bf16[8] custom-call(%q), custom_call_target=' \
    '"tpu_custom_call", metadata={op_name="jit(f)/zoo_attn_core/' \
    'zoo_flash_fwd/pallas_call"}'


def view(op_scopes):
    reduced = {"devices": 2, "busy_s": 2.0, "window_s": 2.1, "ops": [
        ["fusion.7", "", 0.8, 0],         # a role inside the row loop
        ["fusion.8", "", 0.2, 0],         # the row loop's own sum
        ["copy.4", "", 0.3, 0],           # an op_name, no tag
        ["copy-done.5", "", 0.1, 0],      # no op_name at all
        ["convolution.9", "", 1.0, 0],    # a role
        ["c.1", KERNEL, 1.6, 0]]}         # a kernel, by its own text
    return types.SimpleNamespace(
        trace=reduced, result={"op_scopes": op_scopes},
        run=types.SimpleNamespace(root=BENCH, log=lambda m: None))


def test_the_join_tags_and_kernel_texts_decide_what_is_scoped():
    scopes = hlo_scopes.scopes_by_instruction(HLO)
    assert not {"copy.4", "copy-done.5", "fusion.8"} & set(scopes)
    spec = common.load_json("metrics", "train_unscoped_device_pct")
    share = common.load_module("readers", spec["reader"])
    # fusion.8, copy.4, copy-done.5: 0.6 device s over two devices, of
    # 2 s busy; the kernel is scoped by its own text
    assert share.read(spec.get("args", {}), view(scopes)) == \
        pytest.approx(15.0)


def test_no_join_reads_nothing():
    share = common.load_module("readers", "unscoped_share")
    assert share.read({}, view({})) is None
    missing = view({})
    missing.result = {}
    assert share.read({}, missing) is None
    missing.trace = None
    assert share.read({}, missing) is None


@pytest.mark.parametrize("name,pattern,want", [
    ("mixer_proj_device_share_pct", "zoo_(mixer|mla)_proj", 20.0),
    ("optimizer_device_share_pct", "zoo_optimizer", 25.0),
    ("embed_head_loss_device_share_pct", "zoo_(embed|head|loss|lm_loss)",
     None),
])
def test_the_role_shares_read_their_scope(name, pattern, want):
    spec = common.load_json("metrics", name)
    assert spec["reader"] == "scope_share" and \
        spec["args"] == {"pattern": pattern}
    share = common.load_module("readers", "scope_share")
    got = share.read(spec["args"],
                     view(hlo_scopes.scopes_by_instruction(HLO)))
    assert got == (None if want is None else pytest.approx(want))


def test_no_new_scope_is_read_by_an_older_metric():
    """The role scopes fullmatch no pattern that a metric read before
    them, so each of those reads what it read."""
    new = ["zoo_optimizer", "zoo_loss", "zoo_embed", "zoo_mixer_proj",
           "zoo_norm", "zoo_head", "zoo_attn_core"]
    mine = {"train_unscoped_device_pct", "mixer_proj_device_share_pct",
            "embed_head_loss_device_share_pct", "optimizer_device_share_pct",
            "dense_mlp_device_share_pct"}
    for name, spec in common.metric_files():
        pattern = spec.get("args", {}).get("pattern")
        if name in mine or pattern is None:
            continue
        assert not [t for t in new if re.fullmatch(pattern, t)], name
