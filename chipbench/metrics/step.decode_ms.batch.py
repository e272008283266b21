"""Time per decode step (_common.decode_ms) in the batch cells, moving
tok_s."""
from chipbench.metrics._common import decode_ms as read  # noqa: F401
