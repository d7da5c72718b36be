"""``_chunk_step_ms.py`` read in the rag cell, where it moves ``ttft_p95_s``."""
from _chunk_step_ms import read  # noqa: F401
