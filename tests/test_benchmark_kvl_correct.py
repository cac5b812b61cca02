"""The ``keye_vl2`` family's comparison, counted by tier-1: the sound run, the
fp8 control, the hand count of ``flops.py`` and the faults any routed model
could have, of ``benchmark/tests/test_kvl_correct.py``, collected here by
import, not by copy (as ``test_benchmark_lag_correct.py`` collects its cases).
Each case is a process of its own at the rehearsal's sizes on the CPU.  The
planted faults are in ``test_benchmark_kvl_faults.py``: two files, so that two
workers share the minutes.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests",
                     "test_kvl_correct.py")
_spec = importlib.util.spec_from_file_location("benchmark_test_kvl_correct", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

test_a_sound_run_is_correct = _cases.test_a_sound_run_is_correct
test_the_fp8_control_fails_a_limit_that_the_program_passes = _cases.test_the_fp8_control_fails_a_limit_that_the_program_passes
test_the_counts_are_a_hand_count_at_the_rehearsals_sizes = _cases.test_the_counts_are_a_hand_count_at_the_rehearsals_sizes
test_a_fault_any_routed_model_could_have_is_not_correct = _cases.test_a_fault_any_routed_model_could_have_is_not_correct
