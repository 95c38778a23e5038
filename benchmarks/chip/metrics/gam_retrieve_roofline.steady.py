"""The fused kernel's share of its roofline for the batches' semantic work, %
(open-loop cells)."""
from chipbench.readers import gam_retrieve_roofline as read  # noqa: F401
