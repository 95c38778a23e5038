"""How late the load generator submitted each request (submit time minus due
time), median, ms."""
from chipbench.readers import gen_lag_p50_ms as read  # noqa: F401
