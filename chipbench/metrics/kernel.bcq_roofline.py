"""BCQ GEMMs (kernels/bcq_matmul.py, bcq_matmul and bcq_gemv) against
their roofline: for each traced call, the least time its operations
and bytes (from the call's operand shapes) allow on this chip, summed,
over the calls' summed device time, in %."""
from chipbench.lib.roofline import bcq_cost, min_time, parse_shapes


def read(run):
    if run.trace is None:
        return None
    from chipbench.metrics._common import calls
    cs = calls(run, "bcq")
    if not cs:
        return None
    need = sum(min_time(*bcq_cost(parse_shapes(c[2]))[:2], run.peaks)
               for c in cs)
    return 100.0 * need / (sum(c[1] for c in cs) / 1e9)
