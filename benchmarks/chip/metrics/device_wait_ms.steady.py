"""Host time blocked on the device's results (the program's device_wait spans)
per batch in the window, ms (open-loop cells)."""
from chipbench.program_spans import device_wait_ms as read  # noqa: F401
