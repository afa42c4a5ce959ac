"""The benchmark's modules import each other by name from ``chipbench/``."""

import pathlib
import sys

CHIPBENCH = pathlib.Path(__file__).resolve().parents[1]
if str(CHIPBENCH) not in sys.path:
    sys.path.insert(0, str(CHIPBENCH))
