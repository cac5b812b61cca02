"""The ``keye_vl2`` family's planted faults, counted by tier-1: each fault of
``benchmark/tests/test_kvl_correct.py`` (``FAULTS``) gives ``correct: false`` by
the check that is there for it; collected here by import, not by copy.  Each
case is a process of its own at the rehearsal's sizes on the CPU.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests",
                     "test_kvl_correct.py")
_spec = importlib.util.spec_from_file_location("benchmark_test_kvl_faults", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

test_a_broken_timed_path_is_not_correct = _cases.test_a_broken_timed_path_is_not_correct
