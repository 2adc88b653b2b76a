"""Entry point: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout.

Set-up time counts from here.  The checkout's ``src/`` holds the system
under test; without it (or without a TPU) the run exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the script's own directory leaves the path: its module names (trace,
# ...) would shadow the standard library's
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise: keep its
# logs inside the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench", ".logs"))

if __name__ == "__main__":
    from bench.harness import main
    sys.exit(main(t_start=T_START))
