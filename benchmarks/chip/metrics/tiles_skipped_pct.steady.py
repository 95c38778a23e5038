"""Share of kernel tiles the block-union prepass skipped, % (open-loop cells)."""
from chipbench.readers import tiles_skipped_pct as read  # noqa: F401
