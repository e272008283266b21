"""Device idle share (_common.idle_share) in the batch cells, moving tok_s."""
from chipbench.metrics._common import idle_share as read  # noqa: F401
