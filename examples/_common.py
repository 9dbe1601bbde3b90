"""Shared example preamble: put the repo root on ``sys.path``.

The examples run on whatever backend JAX initialises (the TPU when one
is attached); ``JAX_PLATFORMS=cpu`` in the environment forces the CPU.
"""

import os
import sys


def setup() -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
