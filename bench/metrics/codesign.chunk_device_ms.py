"""`engine.chunk_device_ms`, read in the co-design cell, where it moves
`joint_designs_per_s`."""

from benchlib.harness import BENCH, load_file_module

_ENGINE = load_file_module(BENCH / "metrics" / "engine.chunk_device_ms.py")
read = _ENGINE.read
MADE_READING = _ENGINE.MADE_READING
