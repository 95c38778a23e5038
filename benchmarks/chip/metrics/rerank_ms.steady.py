"""The int8 pool's exact re-rank on the host (the program's rerank spans) per
batch in the window, ms (open-loop cells)."""
from chipbench.program_spans import rerank_ms as read  # noqa: F401
