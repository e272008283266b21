"""Time per decode step (_common.decode_ms) in the chat cell, moving
itl_mean_ms."""
from chipbench.metrics._common import decode_ms as read  # noqa: F401
