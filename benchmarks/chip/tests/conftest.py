"""Put the benchmark's own modules and the program on the path, as
``run.py`` does.  Run with ``python -m pytest benchmarks/chip/tests`` from
the repository root, on the CPU (``JAX_PLATFORMS=cpu``)."""
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
for p in (CHIP, CHIP.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
