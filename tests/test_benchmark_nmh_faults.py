"""The ``nemotron_h`` family's planted faults of the latent expert layer, the router and attention, counted by tier-1: each such fault (``FAULTS`` less ``MIXER_FAULTS`` and the generic ones) gives ``correct: false`` by the check that is there for it; cases of
``benchmark/tests/test_nmh_correct.py``, collected here by import, not by copy (as
``test_benchmark_lag_correct.py`` collects its cases).  Each case is a process of its own at the
rehearsal's sizes on the CPU.  The Mamba-2 mixer's are in ``test_benchmark_nmh_mixer_faults.py``.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests",
                     "test_nmh_correct.py")
_spec = importlib.util.spec_from_file_location("benchmark_test_nmh_faults", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

test_a_broken_timed_path_is_not_correct = _cases.test_a_broken_timed_path_is_not_correct
