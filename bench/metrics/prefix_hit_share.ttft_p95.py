"""``_prefix_hit_share.py`` read in the rag cell, where it moves ``ttft_p95_s``."""
from _prefix_hit_share import read  # noqa: F401
