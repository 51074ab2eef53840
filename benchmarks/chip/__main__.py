import sys
import time

STARTED = time.monotonic()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip.run import main  # noqa: E402

sys.exit(main(started=STARTED))
