"""Nemotron-H through the routed family's module against its plain reference, at small sizes on the CPU.

The comparison is ``routed_parity.logits_loss_and_every_gradient_match_the_reference``; the cases are the
architecture's own (``routed_family.ARCHS``), under ids that name it.
"""

import pytest

import routed_family as F
import routed_parity


@pytest.mark.parametrize("name,case", F.cases(lambda arch: sorted(arch.layer_cases), ("nemotron_h",)))
def test_logits_loss_and_every_gradient_match_the_reference(name, case):
    routed_parity.logits_loss_and_every_gradient_match_the_reference(name, case)
