"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero without a result when no
TPU (or too few chips) is found.
"""
import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_root), str(_root / "src")]
    from bench.harness import main

    sys.exit(main(sys.argv[1:]))
