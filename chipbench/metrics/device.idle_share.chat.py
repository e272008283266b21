"""Device idle share (_common.idle_share) in the chat cell, moving
itl_mean_ms."""
from chipbench.metrics._common import idle_share as read  # noqa: F401
