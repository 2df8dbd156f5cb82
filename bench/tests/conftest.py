"""The benchmark's own tests (outside the repository's ``testpaths``):

    python -m pytest bench/tests -q

They import ``bench`` from the repository root and the program from
``src/``."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
