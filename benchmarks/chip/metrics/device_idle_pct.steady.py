"""Share of the traced window in which no operation ran on the device, % (open-
loop cells)."""
from chipbench.readers import device_idle_pct as read  # noqa: F401
