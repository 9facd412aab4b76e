import os
import sys

# the benchmark's own modules (harness, reference) import as top-level
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "bench"))
