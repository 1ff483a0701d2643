"""The benchmark's CPU tests (run them explicitly:
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``); the repository's
tier-1 run collects ``tests/`` only.  Cells run here at ``reduced()``
sizes with the chip check skipped."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
