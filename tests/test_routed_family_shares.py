"""The routed family against its plain references, the shares of the expert layer: the cut (``held_experts``) tied to
the uncut layer, what every share computes alike counted once.

The comparison is ``routed_parity.the_shares_of_the_expert_layer_add_up_to_the_uncut_layer``; the cases are each
architecture's own (``routed_family.ARCHS``), under ids that name the architecture.
"""

import pytest

import routed_parity


@pytest.mark.parametrize("case", list(routed_parity.SHARES))
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(case):
    routed_parity.the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(case)
