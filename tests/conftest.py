import os
import sys
from pathlib import Path

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke tests
# and benches must see the real (single) device; only launch/dryrun.py and
# explicit subprocess tests use placeholder device grids.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
