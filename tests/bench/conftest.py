import os
import sys

# the benchmark's package lives at the repository root, the program in src/
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
