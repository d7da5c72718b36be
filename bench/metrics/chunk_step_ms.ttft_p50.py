"""``_chunk_step_ms.py`` read in the chat cell, where it moves ``ttft_p50_s``."""
from _chunk_step_ms import read  # noqa: F401
