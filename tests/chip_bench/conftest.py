"""The chip benchmark's modules live flat in benchmarks/chip (its entry is
run as a script); its tests import them from there."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "benchmarks" / "chip", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
