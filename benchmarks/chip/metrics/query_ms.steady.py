"""Mean host time of the retriever's query span per batch, ms (open-loop
cells)."""
from chipbench.readers import query_ms as read  # noqa: F401
