"""The benchmark's own tests: ``python -m pytest chipbench/tests``.  They
run on the CPU; the one that needs a TPU skips without one."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
