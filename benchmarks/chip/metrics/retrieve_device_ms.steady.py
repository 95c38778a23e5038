"""Device time per run of the retrieval program, ms (open-loop cells)."""
from chipbench.readers import retrieve_device_ms as read  # noqa: F401
