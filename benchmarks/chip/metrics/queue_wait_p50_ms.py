"""The microbatcher's queue wait per answered request
(QueryResult.queue_wait_s), median, ms."""
from chipbench.readers import queue_wait_p50_ms as read  # noqa: F401
