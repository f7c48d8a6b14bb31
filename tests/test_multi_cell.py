"""The class-wise deployment of the benchmark (`mnist8m-share2-multiclass`)
at a small size on the CPU: the class-wise driver end to end against
`reference_multi.py` at ten and three classes, with its control and planted
faults; the joint re-sort key against the K-key sort; the narrow label row;
the new stats of the spans; the cell's readers.
"""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# tier-1 runs what `benchmark/tests/test_multi_cell.py` runs by path (by its
# path here too: the two files share a name)
_spec = importlib.util.spec_from_file_location(
    "bench_test_multi_cell", os.path.join(BENCH, "tests", "test_multi_cell.py"))
_cell = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cell)
multi_root, sound_multi, traced_multi = (_cell.multi_root, _cell.sound_multi,
                                         _cell.traced_multi)
for _name in dir(_cell):
    if _name.startswith("test_"):
        globals()[_name] = getattr(_cell, _name)
