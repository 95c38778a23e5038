"""Share of the traced window in which the host is inside the program's query
span and no operation runs on the device, % (open-loop cells)."""
from chipbench.program_spans import idle_in_query_pct as read  # noqa: F401
