"""Entry point: ``python3 -m benchmarks.chip.run --workload <cell> ...``;
see :mod:`benchmarks.chip.harness`."""
import time

T_START = time.time()   # set-up is timed from here, before JAX loads

import os  # noqa: E402
import sys  # noqa: E402

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    # the TPU runtime would log to /tmp/tpu_logs, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main(t_start=T_START))
