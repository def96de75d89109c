"""CPU tests of the benchmark at small shapes (``python -m pytest bench/tests``)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

# Small shapes of a declared cell: the same code paths, a graph and widths a
# test run holds. ``wide`` keeps the published widths on a small graph.
SMALL = {"num_nodes": 2048, "avg_degree": 8.0, "feat_dim": 32, "hidden_dim": 32,
         "num_classes": 4}
WIDE = {"num_nodes": 4096, "avg_degree": 16.0}
FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_cell(name: str, wide: bool = False, **cell_over):
    from bench import harness

    cell, cfg = harness.load_cell(name)
    cfg = dict(cfg, **(WIDE if wide else SMALL))
    cell = dict(cell, fanouts=[4, 4, 4], batch_size=128 if wide else 64,
                presample_epochs=1, **cell_over)
    return cell, cfg


def small_run(name: str, seed: int, trace: bool = False, **cell_over) -> dict:
    """A whole run of a declared cell at small shapes on the CPU, with
    made-up peaks: the harness's run without its look for a chip."""
    import time

    import jax

    from bench import harness

    cell, cfg = small_cell(name, **cell_over)
    return harness.run_cell(name, cell, cfg, jax.devices(), FAKE_PEAKS, seed, 1.0,
                            trace, t_start=time.perf_counter())


@pytest.fixture
def small():
    return small_cell
