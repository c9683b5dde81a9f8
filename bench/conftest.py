"""Helpers for the benchmark's CPU tests: cells cut to a size a test
run holds. The tests never look for a chip and never load libtpu."""
import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# the scheduler's cap in these tests: coalesced widths 1, 2, 4
ENGINE = {"serve_max_batch": 4}


def small_cell(name: str, root: Path = ROOT, points: int = 6000,
               rate: float = 30.0):
    """The cell ``name`` found under ``root``, cut to ``points``
    points, 8 partitions and a light load; everything else as
    committed."""
    from bench.run import Cell
    cell = Cell(name, root=root)
    cfg = copy.deepcopy(cell.cfg)
    cfg["points"] = points
    cfg["pinned"].update(partitions=8, n_pad=2048, knots=128, probe=128)
    t = copy.deepcopy(cell.traffic)
    t["rate"] = rate
    t.update(warm_seconds=0.5, warm_rounds=2, check_per_family=6)
    cell.cfg, cell.traffic = cfg, t
    return cell
