"""Tests of the benchmark run on the CPU; the program is imported from
``src`` as the benchmark's own runs import it."""
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
