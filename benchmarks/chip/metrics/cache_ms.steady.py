"""The result cache's lookup and fill around the query (the program's
cache_lookup and cache_fill spans) per batch in the window, ms (open-loop
cells)."""
from chipbench.program_spans import cache_ms as read  # noqa: F401
