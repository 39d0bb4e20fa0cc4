import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
