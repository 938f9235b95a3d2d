"""``benchmark/tests/test_statement_spans.py`` in tier-1: what the new
per-layer metrics read from a program built before their spans. The
cases are that file's own, loaded from where it lies, as
``test_benchmark_index.py`` loads the index's."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "tests", "test_statement_spans.py")
_spec = importlib.util.spec_from_file_location(
    "benchmark_test_statement_spans", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
