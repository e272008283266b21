"""Operations and bytes a call needs, from its shapes, and the chip's
peaks. A roofline share is the least time the chip could take for the
work -- the larger of operations over peak FLOP/s and bytes over peak
bandwidth -- divided by the time it took. Every count here is what the
algorithm needs: each operand read once, each result written once,
attention over the live context only."""
from __future__ import annotations

import json
import re
from pathlib import Path

PEAKS = json.loads((Path(__file__).with_name("peaks.json")).read_text())

ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
            "u8": 1, "pred": 1, "f8e4m3fn": 1, "s4": 0.5, "u4": 0.5}
SHAPE = re.compile(r"\b(bf16|f16|f32|s32|u32|s8|u8|pred)\[([0-9,]*)\]")


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json") from None


def min_time(flops: float, nbytes: float, pk: dict) -> float:
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def parse_shapes(text: str) -> list[tuple[str, tuple]]:
    """[(dtype, dims)] of every array type in an HLO instruction, the
    result first, then the operands in order."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in SHAPE.findall(text)]


def nbytes(dt: str, dims: tuple) -> float:
    n = 1
    for x in dims:
        n *= x
    return n * ITEMSIZE[dt]


FLOATS = ("bf16", "f16", "f32")


def codes_index(shapes) -> int | None:
    """Index of the packed code planes, u32 (bits, K/32, N), among a
    call's shapes (result first), where a float operand comes before
    them; None if there are none."""
    for j in range(2, len(shapes)):
        if shapes[j][0] == "u32" and len(shapes[j][1]) == 3 \
                and shapes[j - 1][0] in FLOATS:
            return j
    return None


def expert_codes_index(shapes) -> int | None:
    """Index of a batched-expert GEMM's code planes, u32
    (E, bits, K/32, N), among a call's shapes, where the operand before
    them is a float x (E, M, K); None if there are none."""
    for j in range(2, len(shapes)):
        (cdt, c), (xdt, x) = shapes[j], shapes[j - 1]
        if cdt == "u32" and len(c) == 4 and xdt in FLOATS \
                and len(x) == 3 and x[0] == c[0] and x[2] == 32 * c[2]:
            return j
    return None


def bcq_cost(shapes) -> tuple[float, float, dict]:
    """A BCQ GEMM call: x (..., K) followed by codes u32 (bits, K/32, N),
    then alphas (G, bits, N) and betas (G, 1, N); the rows M are all of
    x's leading dims."""
    j = codes_index(shapes)
    x, codes = shapes[j - 1][1], shapes[j][1]
    K = x[-1]
    M = 1
    for d in x[:-1]:
        M *= d
    N = codes[2]
    flops = 2.0 * M * K * N
    b = sum(nbytes(*s) for s in shapes)
    return flops, b, {"M": M, "K": K, "N": N, "bits": codes[0]}


def bcq_expert_cost(shapes, routed_rows: int) -> tuple[float, float, dict]:
    """A batched-expert BCQ GEMM call (kernels/bcq_matmul.py:
    bcq_expert_matmul): x (E, M, K), codes u32 (E, bits, K/32, N), then
    alphas and betas. x holds each expert's M rows of capacity, most of
    them padding; only the `routed_rows` (token, expert) pairs routed to
    the call's experts are work. Every operand is read once and the
    result written once."""
    j = expert_codes_index(shapes)
    (E, M, K), codes = shapes[j - 1][1], shapes[j][1]
    N = codes[3]
    flops = 2.0 * routed_rows * K * N
    b = sum(nbytes(*s) for s in shapes)
    return flops, b, {"E": E, "M": M, "K": K, "N": N, "bits": codes[1],
                      "rows": routed_rows}


def paged_attn_cost(live_rows: int, sum_ctx: int, heads: int,
                    kv_heads: int, hd: int, kv_itemsize: float,
                    act_itemsize: float = 2) -> tuple[float, float]:
    """One layer's paged decode for one tick: q and out per live row,
    K and V of every live context token."""
    flops = 4.0 * sum_ctx * heads * hd
    b = (2 * live_rows * heads * hd * act_itemsize
         + 2 * sum_ctx * kv_heads * hd * kv_itemsize)
    return flops, b


def linear_params(dm: dict) -> int:
    """Weights every token passes through, per layer times layers: the
    attention projections, and the gated MLP -- with experts, the
    router and the MLPs of the top-k experts a token is routed to."""
    d, f, hd = dm["d"], dm["f"], dm["hd"]
    attn = d * (dm["heads"] + 2 * dm["kv_heads"]) * hd + dm["heads"] * hd * d
    mlp = 3 * d * f
    if dm.get("experts"):
        mlp = dm["top_k"] * mlp + d * dm["experts"]
    return dm["layers"] * (attn + mlp)


def model_flops(dm: dict, admissions, ticks) -> float:
    """Useful model FLOPs: each new prompt token through every linear
    layer and causal attention over its prefix, one head row per
    admission; each decoded row through the linears, attention over its
    live context and the head."""
    lin = 2.0 * linear_params(dm)
    head = 2.0 * dm["d"] * dm["vocab"]
    att = 4.0 * dm["heads"] * dm["hd"] * dm["layers"]
    total = 0.0
    for _, _, L, shared in admissions:
        new = L - shared
        total += new * lin + head
        total += att * (L * (L + 1) - shared * (shared + 1)) / 2.0
    for _, _, live, ctx in ticks:
        total += live * (lin + head) + att * ctx
    return total
