"""The microbatcher's request_batch span less its query span, per batch in the
window, ms (open-loop cells)."""
from chipbench.program_spans import batch_host_ms as read  # noqa: F401
