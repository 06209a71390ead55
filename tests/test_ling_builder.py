"""What benchmarks/models/ling_hybrid.py refuses: every published key whose
value models/ling_hybrid.py does not compute, by the key's name."""
import pytest
from ling_util import MODEL

from benchmarks.models.ling_hybrid import Builder


@pytest.mark.parametrize("key,value", [
    ("use_nGPT", True), ("value_norm", True), ("up_proj_norm", True),
    ("scale_router_input", True), ("use_bias", True),
    ("use_qkv_bias", True), ("rope_scaling", {"type": "yarn"}),
    ("use_mla_nope", True), ("mtp_use_kda", True), ("q_lora_rank", 1536),
    ("expert_swiglu_limit_list", [0, 0, 0, 4])])
def test_the_builder_refuses_what_the_block_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        Builder(dict(MODEL, **{key: value}))
    # the shared expert's limit likewise; a limit behind the layers that run
    # (MODEL's fifth entries) is no limit on them
    with pytest.raises(ValueError, match="share_expert_swiglu_limit_list"):
        Builder(dict(MODEL, share_expert_swiglu_limit_list=[5, 0, 0, 0]))
    Builder(MODEL)
