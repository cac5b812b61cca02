"""The benchmark's family seam, counted by tier-1: the cases of
``benchmark/tests/test_family_seam.py`` collected here by import, not by copy.

A configuration of another model family comes into ``benchmark/`` by new files
and manifest entries alone, and ``check_manifest.py`` refuses a family that is
not whole.  PR 28 added its family (``families/lfm2_moe/``) under that rule, so
the rule is guarded where the driver counts; the cases now run with two real
families in the tree.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests",
                     "test_family_seam.py")
_spec = importlib.util.spec_from_file_location("benchmark_test_family_seam", _PATH)
_seam = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_seam)

test_a_second_family_is_added_by_files_and_entries_alone = _seam.test_a_second_family_is_added_by_files_and_entries_alone
test_check_manifest_refuses_a_family_that_is_not_whole = _seam.test_check_manifest_refuses_a_family_that_is_not_whole
