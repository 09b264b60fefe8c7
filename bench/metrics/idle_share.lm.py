"""idle_share.lm: ``idle_share`` in the LM cells, where it moves
``tokens_per_s``: a per-layer metric's cells all report the end-to-end
metric it moves, and those cells report no ``frames_per_s``. The window runs
from the first traced decode step's start to the last's; prefills and the
admission's copies count as busy."""
import runpy
from pathlib import Path

read = runpy.run_path(str(Path(__file__).with_name("idle_share.py")))["read"]
