"""Program loads in set-up, in seconds; see ``_setup_jit_s.py``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _setup_jit_s import read  # noqa: E402,F401
