"""Pytest bootstrap for a clean checkout: puts ``src/`` on sys.path so
``import repro`` works without an editable install or PYTHONPATH (the
tier-1 command still sets PYTHONPATH=src; both paths lead to the same
package)."""
import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
