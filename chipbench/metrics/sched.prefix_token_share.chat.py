"""Prefix-token share (sched.prefix_token_share's reader) in the chat
cell, moving itl_mean_ms: a prefix hit shrinks the extend that stalls
every running row."""
from chipbench.lib.spec import metric_reader

read = metric_reader("sched.prefix_token_share").read
