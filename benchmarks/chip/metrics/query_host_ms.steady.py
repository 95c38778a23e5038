"""The program's query span less the device waits inside it, per batch in the
window, ms (open-loop cells)."""
from chipbench.program_spans import query_host_ms as read  # noqa: F401
