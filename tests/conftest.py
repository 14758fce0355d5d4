import os
import sys

# tests see ONE device; multi-device tests force more in their own subprocesses
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
