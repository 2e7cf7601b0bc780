"""Continuous-batching serving demo over the family-generic engine.

    PYTHONPATH=src python examples/serve_lm.py
"""
import sys

from repro.launch.serve import main

sys.exit(main(["--arch", "qwen2.5-3b", "--reduced", "--requests", "6",
               "--slots", "3", "--max-new", "8"]))
