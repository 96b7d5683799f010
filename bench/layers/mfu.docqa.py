"""Model FLOP utilisation of the serving step; see ``_mfu.py``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _mfu import read  # noqa: E402,F401
