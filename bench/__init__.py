"""The chip benchmark of this repository: see run.py and BENCHMARK.json."""
import sys


def log(*a) -> None:
    """A line on standard error, at once."""
    print(*a, file=sys.stderr, flush=True)
