"""Idle share of the device over the traced window; see ``_idle_share.py``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _idle_share import read  # noqa: E402,F401
