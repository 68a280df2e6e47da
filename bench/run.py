#!/usr/bin/env python3
"""Run one cell of the join engine's benchmark on the accelerator.

    python3 bench/run.py --workload lastfm_a1.build --seed 7 --seconds 51 --trace 0

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json`` at the root of the checkout; its configuration, traffic
mix and metric readers are files under ``bench/`` found by those names.
The run makes its data from ``--seed``, warms every shape its traffic uses
(set-up), drives the served path for ``--seconds`` seconds, then checks the
answers against the plain reference in ``bench/reference.py``.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``); the last lines of
standard error give each compared number beside its limit.

Exits 1 without printing a result when JAX finds no TPU or fewer chips
than the cell asks for, and 2 when the ``repro`` package is not in
``src/`` beside ``bench/``.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reduction)")
    args = ap.parse_args(argv)
    # the checkout's own compile cache, whatever the machine had set
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from repro import device  # noqa: F401
    except ImportError as e:
        print(f"bench: the repro package is not in {ROOT / 'src'} ({e})",
              file=sys.stderr)
        return 2
    from bench import harness
    return harness.main(args, PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
