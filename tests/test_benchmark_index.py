"""``benchmark/tests/test_index.py`` in tier-1: ``BENCHMARK.json`` against
the files it names, one case a claim. The cases are that file's own,
loaded from where it lies (under another module name: ``tests/`` has a
``test_index.py`` of its own), so a check added there runs here too."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "tests", "test_index.py")
_spec = importlib.util.spec_from_file_location("benchmark_test_index", _PATH)
_index = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_index)

globals().update({name: case for name, case in vars(_index).items()
                  if name.startswith("test_")})
