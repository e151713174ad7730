"""Smoke-size versions of the chip benchmark's cells for CPU tests: the
cell's own files with the registry model swapped for its smoke variant
(fewer, narrower blocks on 16x16 images), a few images per worker and two
local steps."""
import copy
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import cells  # noqa: E402


def smoke_cell(name: str, monkeypatch, batch: int = 4, local_steps: int = 2,
               registry: str = None):
    """(cell, smoke ArchConfig) with ``repro.configs.get_config`` patched
    to return the smoke config for the cell's registry name."""
    import repro.configs
    cell = copy.deepcopy(cells.load(name))
    reg = registry or cell["config"]["registry"]
    sm = repro.configs.get_config(reg, smoke=True).replace(
        prune_targets=("channel",))
    cell["config"]["registry"] = reg
    cell["config"]["arch"].update(
        blocks=list(sm.cnn_blocks), widths=list(sm.cnn_widths),
        bottleneck=sm.cnn_bottleneck, width_mult=sm.cnn_width_mult,
        img_size=sm.img_size, n_classes=sm.n_classes, gn_size=sm.cnn_gn_size)
    cell["config"]["per_worker_batch"] = batch
    cell["config"]["hsadmm"]["local_steps"] = local_steps
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda n, smoke=False: sm)
    return cell, sm
