"""Pallas TPU kernel: dequant-fused binary-coded GEMM with group-wise
scales.

Computes y = x @ W where
    W[k, n] = sum_i alphas[g(k), n, i] * s_i[k, n] + betas[g(k), n],
g(k) = k // group_size, and the sign bitplanes s_i are packed 32-per-
uint32 along K. The packed codes (bits/16 of the bf16 bytes at 3-bit)
stream HBM->VMEM tile by tile; each tile is expanded to a dense
(BK, BN) weight tile *in VMEM* and fed to the MXU as one bf16 GEMM —
the TPU-native replacement for GPU LUT-GEMM (DESIGN.md §2).
Accumulation over the K grid axis happens in an fp32 VMEM scratch
accumulator.

Group-wise alphas stay a single fused expand: the K-tile's slice of the
(G, N, bits) alpha array is selected by the BlockSpec index map from
the K grid index, so the kernel body only broadcasts each group's
scales over its rows before the one MXU dot — no extra passes, no
gather. Tiling constraint: BK must be a multiple of group_size (several
groups per K-tile) or group_size a multiple of BK (one group spanning
several tiles), and a multiple of 256 for the TPU block rule;
`_group_geometry` picks such a BK near the requested block_k, so any
group_size that is a multiple of the 32-bit pack word works.

Layout notes (TPU-friendly):
  x       (M, K)            -> blocks (BM, BK)
  codes   (bits, K/32, N)   -> blocks (bits, BK/32, BN); K is the
                               second-minor dim so unpacking expands
                               sublanes, keeping N on the 128-wide lane dim
  alphas  (G, N, bits)      -> relaid (G, bits, N), blocks (BG, bits, BN)
  betas   (G, N)            -> relaid (G, 1, N),    blocks (BG, 1, BN)
The wrapper moves the scale group axis G to a leading dim: a (BG, BN)
block of the raw (G, N) betas breaks Mosaic's (8, 128) rule whenever
BG < 8, and a (BN, bits) alpha tile puts `bits` on the lane dim. BK is
a multiple of SUBLANE * WORD = 256, so a codes block fills whole
8-sublane tiles. All MXU dims (BM, BN, BK) default to multiples of 128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw import SUBLANE, WORD

# default tile sizes (repro-lint R004: named, and multiples of the
# SUBLANE/LANE/WORD family — callers override per shape, the kernel
# re-derives legal BK from group_size below)
BLOCK_M = 128
BLOCK_N = 256
BLOCK_K = 256
# decode-shaped (gemv) defaults: wider N/K tiles, 8-row M tile
GEMV_BLOCK_N = 512
GEMV_BLOCK_K = 512
# smallest legal K-tile: its BK/32 packed code rows fill the 8 sublanes
K_UNIT = SUBLANE * WORD


def _expand_w(codes, alphas, betas, *, bits: int, bg: int):
    """Expand one VMEM tile of packed codes + group scales into a dense
    (BK, BN) fp32 weight tile: shift-unpack the sign bitplanes, then
    broadcast each group's scales over its rows. codes (bits, BK/32, BN)
    u32; alphas (BG, bits, BN); betas (BG, 1, BN). Shared by the single-
    matrix and batched-expert kernel bodies."""
    bk32, bn = codes.shape[1], codes.shape[2]
    bk = bk32 * WORD
    shifts = jax.lax.broadcasted_iota(
        jnp.uint32, (1, 1, WORD, 1), 2)                  # (1,1,32,1)
    planes = (codes[:, :, None, :] >> shifts) & jnp.uint32(1)
    # select, not cast: Mosaic has no uint32 -> float32 conversion
    signs = jnp.where(planes == jnp.uint32(1), 1.0, -1.0)

    # expand group scales over their rows: group g covers rows
    # [g*sub, (g+1)*sub) of this K-tile (sub = BK // BG)
    sub = bk // bg
    signs = signs.reshape(bits, bg, sub, bn)
    # scales may arrive bf16 (packed artifacts keep them bf16 in
    # memory); expand in fp32 so accumulation matches fp32-scale runs
    w = jnp.broadcast_to(betas.astype(jnp.float32), (bg, sub, bn))
    for i in range(bits):                                # static unroll
        a_i = alphas[:, i:i + 1, :].astype(jnp.float32)  # (BG, 1, BN)
        w = w + a_i * signs[i]
    return w.reshape(bk, bn)


def _scale_blocks(alphas, betas):
    """Relay group scales for the kernel: alphas (..., G, N, bits) ->
    (..., G, bits, N) and betas (..., G, N) -> (..., G, 1, N), so the
    group axis is a leading block dim and N rides the lanes."""
    return jnp.swapaxes(alphas, -1, -2), betas[..., None, :]


def _dot(x, w):
    """One MXU pass x (BM, BK) @ w (BK, BN) in x's dtype, fp32 result.
    The precision is pinned: bf16 operands take the native bf16 pass
    (Mosaic refuses a higher one), fp32 operands the full-precision
    one, whatever the process-wide default matmul precision says."""
    prec = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(x, w.astype(x.dtype),
                               (((1,), (0,)), ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, codes_ref, alpha_ref, beta_ref, o_ref, acc_ref, *,
            bits: int, nk: int, bg: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _expand_w(codes_ref[...], alpha_ref[...], beta_ref[...],
                  bits=bits, bg=bg)
    acc_ref[...] += _dot(x_ref[...], w)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _expert_kernel(x_ref, codes_ref, alpha_ref, beta_ref, o_ref, acc_ref, *,
                   bits: int, nk: int, bg: int):
    """Batched-expert body: identical math, one extra leading grid axis
    selecting the expert. Every operand block carries a singleton expert
    dim (BlockSpec block size 1 on E) that the body squeezes away."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _expand_w(codes_ref[0], alpha_ref[0], beta_ref[0], bits=bits, bg=bg)
    acc_ref[...] += _dot(x_ref[0], w)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _group_geometry(K: int, G: int, block_k: int):
    """Legalize BK against the scale grouping and the TPU block rule
    (BK a multiple of K_UNIT). Returns (gs, block_k, bg, gtile) where gs
    is the group size (0 for per-channel), bg the groups per K-tile, and
    gtile maps the K grid index to the alpha/beta tile index along G.
    Shared by the single-matrix and batched-expert entries so both
    legalize identically."""
    if G == 1:
        return 0, K_UNIT * max(1, block_k // K_UNIT), 1, lambda k: 0
    if K % G:
        raise ValueError(f"G={G} scale groups must divide K={K}")
    gs = K // G
    if gs % WORD:
        raise ValueError(
            f"group_size={gs} must be a multiple of {WORD} for the "
            f"packed kernel (use the jnp reference path otherwise)")
    if gs > block_k and gs % K_UNIT == 0:
        # one group spans several K-tiles: the largest legal BK <=
        # block_k that divides it keeps every tile inside one group
        block_k = K_UNIT * math.gcd(gs // K_UNIT, max(1, block_k // K_UNIT))
        tiles_per_group = gs // block_k
        return gs, block_k, 1, lambda k: k // tiles_per_group
    # several whole groups per K-tile: BK a multiple of lcm(gs, K_UNIT)
    step = gs * K_UNIT // math.gcd(gs, K_UNIT)
    block_k = step * max(1, block_k // step)
    return gs, block_k, block_k // gs, lambda k: k


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def bcq_matmul(x, codes, alphas, betas, *, block_m=BLOCK_M, block_n=BLOCK_N,
               block_k=BLOCK_K, interpret=False):
    """x (M, K) with K % 32 == 0; codes (bits, K/32, N); alphas
    (G, N, bits); betas (G, N) with G == 1 (per-channel) or G dividing K
    into contiguous groups whose size is a multiple of 32. Returns
    (M, N) in x.dtype. Pads M/N/K to block multiples.
    """
    M, K = x.shape
    bits, KW, N = codes.shape
    G = alphas.shape[0]
    assert KW * WORD == K, (K, KW)
    assert alphas.shape == (G, N, bits), alphas.shape
    assert betas.shape == (G, N), betas.shape

    gs, block_k, bg, gtile = _group_geometry(K, G, block_k)

    # block height must stay a multiple of the 8-sublane tile: round the
    # small-M shortcut up (e.g. M=100 -> bm=104, not 100)
    bm = min(block_m, -(-max(SUBLANE, M) // SUBLANE) * SUBLANE)
    Mp = -(-M // bm) * bm
    Np = -(-N // block_n) * block_n
    Kp = -(-K // block_k) * block_k
    if Mp != M or Kp != K:
        x = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    alphas, betas = _scale_blocks(alphas, betas)
    if Np != N or Kp != K:
        codes = jnp.pad(codes, ((0, 0), (0, (Kp - K) // WORD), (0, Np - N)))
        Gp = Kp // gs if gs else 1
        alphas = jnp.pad(alphas, ((0, Gp - G), (0, 0), (0, Np - N)))
        betas = jnp.pad(betas, ((0, Gp - G), (0, 0), (0, Np - N)))

    nk = Kp // block_k
    grid = (Mp // bm, Np // block_n, nk)
    s_index = lambda i, j, k: (gtile(k), 0, j)           # K-tile -> groups

    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, nk=nk, bg=bg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((bits, block_k // WORD, block_n),
                         lambda i, j, k: (0, k, j)),
            pl.BlockSpec((bg, bits, block_n), s_index),
            pl.BlockSpec((bg, 1, block_n), s_index),
        ],
        out_specs=pl.BlockSpec((bm, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, codes, alphas, betas)
    return out[:M, :N]


def bcq_gemv(x, codes, alphas, betas, *, block_n=GEMV_BLOCK_N,
             block_k=GEMV_BLOCK_K, interpret=False):
    """Decode-shaped variant: tiny M (1..8 rows). Pads M to the 8-sublane
    tile and uses wider N/K blocks (the op is bandwidth-bound: the packed
    codes dominate bytes; x and y are negligible)."""
    return bcq_matmul(x, codes, alphas, betas, block_m=SUBLANE,
                      block_n=block_n, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def bcq_expert_matmul(x, codes, alphas, betas, *, block_m=BLOCK_M,
                      block_n=BLOCK_N, block_k=BLOCK_K, interpret=False):
    """Batched-expert GEMM: one launch covers an MoE layer's whole
    expert stack instead of E separate dispatches (or a full dequant of
    every expert's W). x (E, M, K); codes (E, bits, K/32, N); alphas
    (E, G, N, bits); betas (E, G, N). Returns (E, M, N) in x.dtype.

    The expert axis becomes a leading parallel grid dimension with block
    size 1: each (e, i, j, k) step streams expert e's packed K-tile into
    VMEM and runs the same expand-then-one-GEMM body as `bcq_matmul`
    (the kernel squeezes the singleton expert dim). Group legalization,
    padding and the fp32 accumulator are shared with the single-matrix
    entry, so the two stay numerically identical per expert.
    """
    E, M, K = x.shape
    bits, KW, N = codes.shape[-3:]
    G = alphas.shape[1]
    assert KW * WORD == K, (K, KW)
    assert codes.shape == (E, bits, KW, N), codes.shape
    assert alphas.shape == (E, G, N, bits), alphas.shape
    assert betas.shape == (E, G, N), betas.shape

    gs, block_k, bg, gtile = _group_geometry(K, G, block_k)

    bm = min(block_m, -(-max(SUBLANE, M) // SUBLANE) * SUBLANE)
    Mp = -(-M // bm) * bm
    Np = -(-N // block_n) * block_n
    Kp = -(-K // block_k) * block_k
    if Mp != M or Kp != K:
        x = jnp.pad(x, ((0, 0), (0, Mp - M), (0, Kp - K)))
    alphas, betas = _scale_blocks(alphas, betas)
    if Np != N or Kp != K:
        codes = jnp.pad(
            codes, ((0, 0), (0, 0), (0, (Kp - K) // WORD), (0, Np - N)))
        Gp = Kp // gs if gs else 1
        alphas = jnp.pad(alphas, ((0, 0), (0, Gp - G), (0, 0), (0, Np - N)))
        betas = jnp.pad(betas, ((0, 0), (0, Gp - G), (0, 0), (0, Np - N)))

    nk = Kp // block_k
    grid = (E, Mp // bm, Np // block_n, nk)
    s_index = lambda e, i, j, k: (e, gtile(k), 0, j)

    out = pl.pallas_call(
        functools.partial(_expert_kernel, bits=bits, nk=nk, bg=bg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, block_k), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bits, block_k // WORD, block_n),
                         lambda e, i, j, k: (e, 0, k, j)),
            pl.BlockSpec((1, bg, bits, block_n), s_index),
            pl.BlockSpec((1, bg, 1, block_n), s_index),
        ],
        out_specs=pl.BlockSpec((1, bm, block_n),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, codes, alphas, betas)
    return out[:, :M, :N]
