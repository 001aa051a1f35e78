#!/usr/bin/env python3
"""One run of one cell of the Janus chip benchmark.

  python3 bench/run.py --workload vit-l384.4g-pruned --seed 7 --seconds 30 --trace 0

Run from the root of a checkout, on a machine with a TPU. The cell's
configuration, traffic mix and limits are found by its name in
``BENCHMARK.json``. Set-up (``setup_s``) runs from process start to the
first timed tick: weights and frames made on the chip from the seed, the
fleet runtime built, two warm-up ticks that compile or load from the
compile cache (``.jax_cache`` in the checkout) every program the window
runs. Then ticks run back to back for ``--seconds``. ``--trace 1`` traces a
few more ticks and reports the per-layer metrics instead of the end-to-end
ones. Every run checks the logits of frames from the window against the
plain reference. Readings go to earlier lines; the last line is one JSON
object. Without a TPU the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
import pathlib  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
