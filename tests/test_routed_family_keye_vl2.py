"""The routed family against its plain references, logits, loss and every gradient: Keye-VL-2.0's language model (attention whose keys a learned indexer chooses, the indexer's own loss).

The comparison is ``routed_parity.logits_loss_and_every_gradient_match_the_reference``; the cases are the
architecture's own (``routed_family.ARCHS``), under ids that name it.
"""

import pytest

import routed_family as F
import routed_parity


@pytest.mark.parametrize("name,case", F.cases(lambda arch: sorted(arch.layer_cases), ("keye_vl2",)))
def test_logits_loss_and_every_gradient_match_the_reference(name, case):
    routed_parity.logits_loss_and_every_gradient_match_the_reference(name, case)
