import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for d in (os.path.dirname(BENCH), BENCH):
    if d not in sys.path:
        sys.path.insert(0, d)
