"""Prefill and extend time per thousand new prompt tokens
(step.prefill_ms_per_ktok's reader) in the chat cell, moving
itl_mean_ms: each admission's extend stalls every running row."""
from chipbench.lib.spec import metric_reader

read = metric_reader("step.prefill_ms_per_ktok").read
