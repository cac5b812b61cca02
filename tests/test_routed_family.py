"""The routed family against its plain references, logits, loss and every gradient: the architectures whose attention
sees every earlier key (LFM2-MoE, DeepSeek-V2-Lite, Qwen3-Next).

The comparison is ``routed_parity.logits_loss_and_every_gradient_match_the_reference``; the cases are each
architecture's own (``routed_family.ARCHS``), under ids that name the architecture.
"""

import pytest

import routed_family as F
import routed_parity


@pytest.mark.parametrize("name,case", F.cases(lambda arch: sorted(arch.layer_cases), ("lfm2_moe", "deepseek_v2", "qwen3_next")))
def test_logits_loss_and_every_gradient_match_the_reference(name, case):
    routed_parity.logits_loss_and_every_gradient_match_the_reference(name, case)
