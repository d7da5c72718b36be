"""``_prefix_hit_share.py`` read in the chat cell, where it moves ``ttft_p50_s``."""
from _prefix_hit_share import read  # noqa: F401
