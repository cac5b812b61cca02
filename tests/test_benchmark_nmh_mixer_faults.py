"""The ``nemotron_h`` family's planted faults of the Mamba-2 mixer, counted by tier-1: each fault of ``MIXER_FAULTS`` (the state reset at every chunk boundary, the skip left out, the norm before the gate or over a head's channels, ``dt`` without its softplus, the convolution's bias left out) gives ``correct: false`` by the check that is there for it; cases of
``benchmark/tests/test_nmh_correct.py``, collected here by import, not by copy (as
``test_benchmark_lag_correct.py`` collects its cases).  Each case is a process of its own at the
rehearsal's sizes on the CPU.  The expert layer's are in ``test_benchmark_nmh_faults.py``.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests",
                     "test_nmh_correct.py")
_spec = importlib.util.spec_from_file_location("benchmark_test_nmh_mixer_faults", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

test_a_broken_mixer_is_not_correct = _cases.test_a_broken_mixer_is_not_correct
