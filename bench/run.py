#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration and its traffic
mix are found by name from ``BENCHMARK.json`` (see ``bench/harness/
cells.py``). The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, each
number compared beside its limit. Where JAX finds no accelerator, fewer
chips than the cell asks for, or no program next to the benchmark, it
exits non-zero and prints no result.
"""
import time

_T0 = time.perf_counter()      # set-up is timed from here

import sys                     # noqa: E402
from pathlib import Path       # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program (src/repro) beside {BENCH}; nothing ran",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from harness import runner
    runner.cache_env(ROOT)
    return runner.main(sys.argv[1:], t_start=_T0)


if __name__ == "__main__":
    sys.exit(main())
