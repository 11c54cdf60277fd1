"""Small cells for the benchmark's CPU tests: the real manifest entries
with their configurations cut to a size the CPU runs in seconds."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SMALL = {"num_tasks": 64, "dim": 32, "capacity": 64, "max_rows_per_task": 16,
         "rows_mean": 8, "rows_std": 4}


def small_cell(workload: str, **traffic):
    from bench import spec

    cell = spec.cell(workload)
    tr = dict(cell.traffic, **traffic)
    return cell._replace(config=dict(cell.config, **SMALL), traffic=tr)


@pytest.fixture
def cpu_device():
    import jax

    return jax.devices("cpu")[:1]
