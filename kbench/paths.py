"""Where the benchmark keeps its files.  Everything is relative to the
checkout that holds this directory; nothing is written outside it."""

import os

KBENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(KBENCH)
CACHE = os.path.join(KBENCH, "cache")    # tokenizers, reference expectations
OUT = os.path.join(KBENCH, "out")        # logs, reports, traces of the last runs
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
