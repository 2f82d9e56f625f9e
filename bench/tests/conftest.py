"""The benchmark's modules import each other as top-level modules, as
they do under ``python3 bench/run.py``; put ``bench/`` and the program
on the path."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
