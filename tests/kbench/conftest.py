"""The benchmark's modules are flat files under kbench/: put that
directory on the path for the tests of this directory."""

import os
import sys

KBENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "kbench")
if KBENCH not in sys.path:
    sys.path.insert(0, KBENCH)
