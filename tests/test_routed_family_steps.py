"""The routed family against its plain references, two train steps: loss, load, parameter change, the router bias's
step or the balance term, the held-out loss.

The comparison is ``routed_parity.two_train_steps_match_the_reference``; the cases are each architecture's own
(``routed_family.ARCHS``), under ids that name the architecture.
"""

import pytest

import routed_family as F
import routed_parity


@pytest.mark.parametrize("name", list(F.ARCHS))
def test_two_train_steps_match_the_reference(name):
    routed_parity.two_train_steps_match_the_reference(name)
