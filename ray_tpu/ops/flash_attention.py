"""Flash attention: Pallas TPU kernels (fwd + bwd) with a JAX oracle.

Memory-efficient exact attention — O(S) memory via online softmax — the
building block under both the single-chip attention path and (composed with
`parallel.ring_attention` over the sp axis) long-context training. The
kernels follow the Pallas TPU model: Q blocks ride the grid, K/V stream
through VMEM, matmuls hit the MXU in fp32 accumulation
(guide: /opt/skills/guides/pallas_guide.md — grid/BlockSpec, fori_loop,
preferred_element_type).

Layouts: public API takes [B, S, H, D]; kernels run [B, H, S, D].
GQA: K and V reach the kernels at the KV heads' count; a grid row of query
head h reads KV head h // group (`_spec`), and the backward rule sums dk and
dv over each group. No copy at the query heads' count is written to HBM.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Reference (the oracle tests compare against; the path on non-TPU backends)
# --------------------------------------------------------------------------

def _reference_attention(q, k, v, causal, scale: float):
    # q,k,v: [B,H,S,D]; `causal` a bool or a mask rule (below): the DENSE mask
    mask = _rule(causal)
    s_q, s_k = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        kept = mask.keep(jnp.arange(s_q)[:, None] + (s_k - s_q),
                         jnp.arange(s_k)[None, :])
        scores = jnp.where(kept[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# --------------------------------------------------------------------------
# Mask rules: which (query, key) pairs a call keeps, known statically
# --------------------------------------------------------------------------
# A rule is a hashable value with
#   keep(q_pos, k_pos) -> bool, elementwise over int arrays of positions
#       (numpy in the schedule, traced int32 tiles in the kernels and the
#       oracle; operators and `.astype` only, so one body serves all three);
#   tile(q0, nq, k0, nk) -> (any score of the tile kept, every one kept);
#   needed(s_q, s_k) -> the scores it keeps;
#   scope: a `jax.named_scope` for the call (names its Pallas events), or None.
# Query positions are aligned to the keys' END (row r of s_q stands at
# r + s_k - s_q, the oracle's tril(k=s_k - s_q)). `causal=True` is the rule
# `CAUSAL`, `causal=False` no rule: every score kept.


class Causal(NamedTuple):
    """Query i sees the keys up to its own position: what `causal=True`
    stands for."""
    scope = None

    def keep(self, q_pos, k_pos):
        return q_pos >= k_pos

    def tile(self, q0, nq, k0, nk):
        return k0 <= q0 + nq - 1, k0 + nk - 1 <= q0

    def needed(self, s_q, s_k):
        return sum(min(max(r + s_k - s_q + 1, 0), s_k) for r in range(s_q))


CAUSAL = Causal()


class BlockDiffusion(NamedTuple):
    """Block-diffusion training over the concatenation [x_t ; x_0] of a
    noised and a clean copy of `length` tokens (BD3-LM, arXiv:2503.09573):
    position i < length is noised, i >= length clean, and its block is
    (i mod length) // block. Query i sees key j iff they are in the same
    half and block (block-diagonal inside each half), or j is clean and in
    an EARLIER block (block-causal inside x_0, offset block-causal from x_t
    to x_0). Neither causal nor full: x_0 rows never see x_t columns, x_t
    rows see their own block both ways. Keeps length^2 + length x block of
    the (2 length)^2 scores."""
    length: int
    block: int
    scope = "bd.attend"

    def _block_of(self, pos):
        clean = pos >= self.length
        inside = pos - clean.astype(pos.dtype) * self.length
        if self.block & (self.block - 1) == 0:
            return clean, inside >> (self.block.bit_length() - 1)
        return clean, inside // self.block

    def keep(self, q_pos, k_pos):
        q_clean, q_blk = self._block_of(q_pos)
        k_clean, k_blk = self._block_of(k_pos)
        return ((q_clean == k_clean) & (q_blk == k_blk)) \
            | (k_clean & (k_blk < q_blk))

    def tile(self, q0, nq, k0, nk):
        import numpy as np

        end = 2 * self.length
        q = np.arange(q0, min(q0 + nq, end), dtype=np.int32)[:, None]
        k = np.arange(k0, min(k0 + nk, end), dtype=np.int32)[None, :]
        if not q.size or not k.size:
            return False, False
        kept = self.keep(q, k)
        return bool(kept.any()), bool(kept.all())

    def needed(self, s_q, s_k):
        if s_q != s_k or s_q != 2 * self.length \
                or self.length % self.block:
            raise ValueError(
                f"{self} is a rule over 2 x length positions, queries and "
                f"keys alike, in whole blocks; got {s_q} x {s_k}")
        return self.length * self.length + self.length * self.block


class SlidingWindow(NamedTuple):
    """Causal within a window: query i sees the `window` keys that end at
    its own position, i - window < j <= i (itself included). Keeps
    min(i + 1, window) scores a row. A tile below the diagonal is kept
    whole only while the window spans it; the window's TRAILING tile is a
    strict upper triangle (the keys a query has just lost), so it keeps
    scores off its diagonal sub-tiles and is never a `DIAGONAL` step. What
    a row of tiles keeps is a BAND: each 128-row group's keys are one run
    of sub-tiles, a sub-tile on from the group's before (at window = tile
    = 512: 5 of the 8 its own and the trailing tile span), which an
    unrolled plan runs as ONE `Band` step in their place."""
    window: int
    scope = "swa.attend"

    def keep(self, q_pos, k_pos):
        return (q_pos >= k_pos) & (q_pos - k_pos < self.window)

    def tile(self, q0, nq, k0, nk):
        # q - k over the tile is every integer of [nearest, farthest]
        nearest, farthest = q0 - (k0 + nk - 1), q0 + nq - 1 - k0
        return (farthest >= 0 and nearest < self.window,
                nearest >= 0 and farthest < self.window)

    def needed(self, s_q, s_k):
        return sum(min(max(r + s_k - s_q + 1, 0), self.window, s_k)
                   for r in range(s_q))


class EvaWindows(NamedTuple):
    """EVA attention (Zheng et al., ICLR 2023, as EvaByte ships it) over a
    key axis that is NOT the query axis: the keys are the concatenation
    [length // chunk chunk summaries ; length bytes] and the `length`
    queries stand, as every call's do, at the keys' END, so row n's position
    is its own byte key. Byte n (window n // window) sees, in ONE softmax,
    the bytes of its own window up to itself, and the summaries of every
    chunk of every EARLIER window; never a summary of its own window, so no
    summary it sees holds a byte later than itself. `length` is whole chunks
    and `window` whole chunks; the last window may be partial (its summaries
    are keys no query sees). Keeps, at length 32,768, window 2,048, chunk
    16, 33,570,816 local + 31,457,280 summary scores a (batch, head) where
    causal attention keeps 536,887,296."""
    length: int
    window: int
    chunk: int
    scope = "eva.attend"

    @property
    def summaries(self):
        return self.length // self.chunk

    def _over(self, pos, n):
        """pos // n, a shift where n is a power of two (the kernels')."""
        if n & (n - 1) == 0:
            return pos >> (n.bit_length() - 1)
        return pos // n

    def keep(self, q_pos, k_pos):
        ns = self.summaries
        q_win = self._over(q_pos - ns, self.window)
        local = (k_pos >= ns) & (k_pos <= q_pos) \
            & (self._over(k_pos - ns, self.window) == q_win)
        earlier = self._over(k_pos, self.window // self.chunk) < q_win
        return local | ((k_pos < ns) & earlier)

    def tile(self, q0, nq, k0, nk):
        ns, w = self.summaries, self.window
        n0, n1 = max(q0 - ns, 0), min(q0 + nq - ns, self.length) - 1
        k1 = min(k0 + nk, ns + self.length) - 1
        k0 = max(k0, 0)
        if n0 > n1 or k0 > k1:
            return False, False
        some, every = False, True
        if k0 < ns:  # summaries c0..c1: kept where their window is earlier
            per = w // self.chunk
            c0, c1 = k0, min(k1, ns - 1)
            some |= c0 // per < n1 // w
            every &= c1 // per < n0 // w
        if k1 >= ns:  # bytes j0..j1: the query's own window, up to itself
            j0, j1 = max(k0 - ns, 0), k1 - ns
            some |= any(
                min(n1, win * w + w - 1) >= max(j0, win * w)
                for win in range(max(n0 // w, j0 // w),
                                 min(n1 // w, j1 // w) + 1))
            every &= n0 // w == n1 // w == j0 // w == j1 // w and j1 <= n0
        return some, some and every

    def kept(self, s_q, s_k):
        """-> (local, summary) scores kept a (batch, head)."""
        ns, w = self.summaries, self.window
        if s_q != self.length or s_k != ns + self.length \
                or self.length % self.chunk or w % self.chunk:
            raise ValueError(
                f"{self} is a rule for {self.length} queries over "
                f"{ns} summaries + {self.length} bytes of whole chunks; "
                f"got {s_q} x {s_k}")
        whole, rest = divmod(self.length, w)
        local = whole * (w * (w + 1) // 2) + rest * (rest + 1) // 2
        summary = (w // self.chunk) * (
            w * (whole * (whole - 1) // 2) + rest * whole)
        return local, summary

    def needed(self, s_q, s_k):
        return sum(self.kept(s_q, s_k))


def _rule(causal, mask=None):
    """The rule a call runs under: `mask` if given, else what `causal`
    (a bool, or a rule already) stands for."""
    if mask is not None:
        return mask
    if causal is True:
        return CAUSAL
    return None if causal is False else causal


# --------------------------------------------------------------------------
# Block schedule: which steps each grid row runs, and which of them need a mask
# --------------------------------------------------------------------------

def _cdiv(a, b):
    return (a + b - 1) // b


def _clamp_block(block, seq):
    # Clamp to the sequence, then round DOWN to a lane-aligned multiple of
    # 128 (Mosaic tiling): min(512, 300) = 300 would otherwise make an
    # unaligned BlockSpec. Sequences <=128 keep block == seq, the
    # long-standing short-seq path.
    b = min(block, seq)
    return (b // 128) * 128 if b > 128 else b


def _step_width(own, other, rule):
    """Width of a loop step along the axis a grid row walks. Under a rule:
    no more than the block the row owns, so a step the rule cuts is at most
    square and is never executed at twice its visible size (`other` when
    the owned block does not divide it). No rule: the other block."""
    return own if rule is not None and other % own == 0 else other


# A step's kind, where `masked` stands: False, no mask at all; True, the
# rule's predicate over the whole tile; DIAGONAL (truthy: a masked step too),
# the predicate over the tile's aligned `_SUB` x `_SUB` diagonal sub-tiles
# alone, which hold every score the rule keeps of the tile; a `Band` or a
# `Triangle` (below).
DIAGONAL = "diagonal"
_SUB = 128


class Band(NamedTuple):
    """A step's kind too (truthy: a masked step): a grid row's ONLY step,
    in place of the whole tiles it would walk. Each `_SUB`-row group of the
    owned block runs against its own `run` sub-tiles of the walked axis,
    which hold every score the rule keeps of the group: group g's start
    `shift + g * _SUB` positions from the owned block's first, under the
    predicate. Rows of one kind run ONE body, whichever the row."""
    shift: int
    run: int


class Triangle(NamedTuple):
    """A step's kind too (truthy: a masked step): a square tile of n x n
    sub-tiles of which the rule keeps nothing on one side of the sub-tile
    diagonal. Each HALF of the owned block's `_SUB`-row groups is one
    masked step against the walked axis as far as the half's own end
    (`leading`: a causal tile's queries) or from its start on (its keys,
    which dk/dv owns; a window's trailing tile): the quarter of the tile
    beyond the first half's end, or before the second's start, is not run
    (`_halves`: 12 of 16 sub-tiles at 512 x 512, 10 of which hold scores)."""
    leading: bool


def _halves(n, leading):
    """A `Triangle` step's two pieces over n x n sub-tiles, as (groups from,
    to; walked sub-tiles from, to)."""
    half = (n + 1) // 2
    return [(lo, hi, 0, hi) if leading else (lo, hi, lo, n)
            for lo, hi in ((0, half), (half, n))]


class SharedRows(NamedTuple):
    """The grid rows of a loop plan that are ONE row, placed by the grid row:
    `rows` run `steps`, (step index minus row x `stride`, masked), as one
    branch of straight-line code (`_run_row`) in the order given: the tiles
    the rule keeps whole first, with no mask, then those it cuts, whole
    under the predicate; `stride`: the steps of the plan's width an owned
    block spans. Under `SlidingWindow(4096)` at S 16,384 in tiles of 512: 24
    of the 32 rows, each seven tiles with no mask, then the window's
    trailing tile and the row's own."""
    rows: tuple
    stride: int
    steps: tuple


class KernelSchedule(NamedTuple):
    """One kernel's loop plan over one (batch, head): `tiles` are
    (q_start, q_rows, k_start, k_cols, masked), one per loop step, and
    `rows` the same steps by grid row, as (step index, masked); a tile the
    rule keeps nothing of is no step at all (`steps_skipped` counts them).
    `static`: every grid row's steps run as straight-line code, each masked
    only if it needs it, and as `DIAGONAL` (`steps_diagonal` of the
    `steps_masked`) where the rule keeps nothing off the tile's diagonal
    sub-tiles, and a row whose groups each keep one run of sub-tiles, a
    sub-tile on from the group before, as ONE `Band` step (`steps_band` of
    the `steps_masked`; its tile starts where group 0's run does and spans
    all the groups' runs, its row entry holds the index of the first whole
    tile it stands for), and a tile the rule cuts along its sub-tile
    diagonal as a `Triangle` (`steps_triangle` of the `steps_masked`);
    otherwise loops over `table`, a grid row's steps (`_run_row`): first
    several a body, as straight-line code, for each size of `body` in turn
    `bodies[row]`'s count of bodies of that many steps, the row's whole
    tiles, with no mask; then the rest one an iteration, each masked whole if
    any step of the plan is (`_LOOP_BODY`; `body` (): all of them so); but
    for the rows of `shared` (None: no such rows), the plan's majority of
    same-shaped rows, which run one unrolled branch between them, each step
    masked only if it needs it. `rows` and `tiles` hold a row's steps in the
    order and under the mask they run: the counts are of what runs."""
    width: int
    static: bool
    tiles: tuple
    rows: tuple
    steps_unmasked: int
    steps_masked: int
    steps_diagonal: int
    steps_band: int
    steps_triangle: int
    steps_skipped: int
    executed_over_needed: float
    shared: Optional[SharedRows] = None
    body: tuple = ()
    bodies: tuple = ()

    @property
    def steps_shared(self):
        """The steps, of `tiles`, that run in the shared branch."""
        return len(self.shared.rows) * len(self.shared.steps) \
            if self.shared else 0

    @property
    def steps_loop_body(self):
        """The steps, of `tiles`, that run inside a loop's body of several
        steps."""
        return sum(k * n for row in self.bodies
                   for k, n in zip(self.body, row) if k > 1)

    @property
    def table(self):
        """What a loop plan's kernel reads from SMEM, int32 [grid rows, 1 +
        the sizes of `body` + most steps a row]: the steps the row runs one
        at a time, its bodies of each size, then the steps' indices, the
        bodies' first."""
        import numpy as np

        lead = 1 + len(self.body)
        out = np.zeros((len(self.rows), lead + max(map(len, self.rows))),
                       np.int32)
        for i, steps in enumerate(self.rows):
            if self.body:
                out[i, 1:lead] = self.bodies[i]
            out[i, 0] = len(steps) - np.dot(out[i, 1:lead], self.body)
            out[i, lead:lead + len(steps)] = [j for j, _ in steps]
        return out


# What of a plan may be how large for its kernel to be unrolled, measured on
# the v5e at 512 x 512 (PERF.md §6, PR 26, 34, 38). Forward and dq (dq runs
# the forward's plan): the LONGEST grid row, 8 steps: 4 (causal S 2048) is
# the gain this exists for, 8 (S 4096, 36 steps in all) takes the forward
# from 5.80 to 4.03 ms and dq gains too (a row of 9 exists only in plans past
# `_STATIC_STEPS`: `_SHARED_BUDGET`). dk/dv: the plan's steps IN ALL, 28:
# its code grows faster, and at the 36 of causal S 4096 it collapsed (8.7 ->
# 29.5 ms) where the block-diffusion call at 2 x 2048 (24: rows of 1, 1, 1,
# 1, 8, 6, 4, 2) goes from 7.50 to 5.20 ms, causal S 3072 (21) from 5.21 to
# 4.31 and S 3584 (28) from 6.83 to 5.65: a row of 8 is not what collapsed.
# A `Triangle` step is more straight-line code than the whole step it stands
# for and counts as ONE all the same: measured at twice the pieces it has now
# (one a 128-row group, PR 51) dk/dv gained at every plan under the budget
# (the block-diffusion call's 24 steps, 8 of them triangles, 4.58 -> 4.17 ms;
# causal S 3072, 6 of 21, and S 3584, 7 of 28, at batch 2: 2.13 -> 2.02 and
# 2.80 -> 2.66), and a forward row of 8 (S 4096) went from 1.93 to 1.96
# while its dq went from 2.59 to 2.45.
_STATIC_BUDGET = {"fwd": (max, 8), "dkv": (sum, 28)}
# And no plan of more steps IN ALL than the largest measured to gain is
# unrolled, the forward's neither: every grid row's branch is code of the one
# kernel, and `EvaWindows` at S 32,768 (64 rows of at most 8 steps, 304 in
# all: 54 MB of generated code) ran the forward at 269 ms and dq at 330 where
# the loop plans take 17.0 and 17.6 (PERF.md §6, PR 57). 36 is causal S 4096
# (above); between 36 and 304 nothing is measured.
_STATIC_STEPS = 36
# The longest row that a loop plan's same-shaped rows run as ONE shared
# branch (`SharedRows`), by kernel (dq runs the forward's plan); 0: never.
# The kernel's code is then that row's steps beside one loop body, however
# many the rows, so neither budget above speaks of it. 9 is the one length
# measured on the v5e (PERF.md §6, PR 58: q [1, 16384, 28, 128] over 4 KV
# heads under `SlidingWindow(4096)`, 24 of 32 rows of 9 steps; ms a call, the
# loop and then the branch): forward 8.70 -> 6.08, dq 9.02 -> 7.88, dk/dv
# 13.18 -> 10.91. Two things that were measured decide its form. The ORDER:
# with the trailing tile first, as the row walks, and as a `Triangle`, the
# forward read 8.52 (the halves' carries are joined again, and every step
# after that ran slower); the tiles with no mask first, 6.17. And what the
# cell's SET-UP pays for every body a kernel traces and lowers, in each of
# its 15 lowerings: 9 steps and 2 triangles as python's straight-line code
# were +11.6 s of 58.8 (the bound is 10%); each run of steps of one kind as
# ONE traced body, unrolled at lowering, and the triangles still (dq 7.66,
# dk/dv 10.42: +0.6% of the cell's tokens a second) +4.6 s; the cut tiles
# whole under the mask, as they are now, 56.7 / 58.1 s where the parent read
# 57.5 / 59.1. The mask is no cost in a kernel alone (no mask at all, wrong,
# for the timing: 5.97 / 7.83 / 10.68), but on all 9 steps it cost the cell
# 1.3%.
_SHARED_BUDGET = {"fwd": 9, "dkv": 9}
# How many steps a body of a loop plan's loop runs as straight-line code, by
# kernel (dq runs the forward's plan): a row's steps fill bodies of the first
# size, what is left bodies of the next, and the rest run one an iteration
# ((): all of them, the loop before PR 60). What fills bodies is the row's
# WHOLE tiles alone, first in the row and with NO mask. One traced step in a
# `fori_loop(unroll=True)` a size, so a kernel's code is 4 + 2 + 1 steps
# however long the plan. Measured on the v5e (PERF.md §6, PR 60;
# `tools/flash_chip_check.py --sweep`), forward + dq + dk/dv ms a call, the
# loop of one step and then the form: `CAUSAL` at q [1, 8192, 48, 128] over 8
# KV heads 27.22 -> 24.52 (the forward 7.38 -> 6.47, dq 8.18 -> 7.50, dk/dv
# 11.66 -> 10.55), at [1, 16384, 28, 128] over 4 60.28 -> 51.96 (16.30 ->
# 13.00, 18.17 -> 16.40, 25.81 -> 22.56), at [1, 32768, 32, 64] over 8 268.4
# -> 225.8 (72.5 -> 55.0, 81.4 -> 72.5, 114.6 -> 98.4); `EvaWindows(32768,
# 2048, 16)` at [1, 32768, 32, 128] 56.50 -> 48.64 (17.02 -> 14.49, 17.57 ->
# 14.75, 21.92 -> 19.40). The other forms, the three kernels' sum against
# the loop's at those four calls: (2,) -8.3 / -10.8 / -12.5 / -14.6%; (4,)
# -10.1 / -13.9 / -15.9 / -9.1% (EVA's forward rows hold at most 7 whole
# tiles and 64 of them fewer than 4: 96 of 304 steps in bodies, 160 at (4,
# 2)); (8,) -7.3 / -13.3 / -16.7 / -3.1%; (4, 2) -9.9 / -13.8 / -15.9 /
# -13.9%, the one form within a point of the best at all four; (8, 4, 2) -9.5
# / -14.5 / -17.3 / -12.6%; a last size of 1 (the whole tiles that fill no
# body with no mask either, a third loop) (4, 2, 1) -8.7 / -13.1 / -15.5 /
# -14.7%. Bodies of ANY of a row's steps, in the row's order and under the
# plan's mask (tried, not kept): (2,) -6.4 / -7.8 / -8.3 / -0.2%, (4,) -8.5 /
# -10.8 / -12.0 / -1.8%, (8,) -7.0 / -10.9 / -13.0 / +0.7%: the predicate is
# a quarter of what the straight line gains under `CAUSAL` and all of it
# under `EvaWindows`, whose predicate is a dozen vector ops a score. The 8
# edge rows of `SlidingWindow(4096)` at [1, 16384, 28, 128]: 24.87 -> 24.6.
_LOOP_BODY = {"fwd": (4, 2), "dkv": (4, 2)}


@functools.lru_cache(maxsize=256)
def _block_schedule(s_q, s_k, block_q, block_k, rule):
    offset = s_k - s_q
    needed = s_q * s_k if rule is None else rule.needed(s_q, s_k)

    def plan(kernel, width, n_rows, n_steps, tile, length):
        """tile(row, step) -> (q0, nq, k0, nk); `length`: the true length of
        the axis the row walks."""

        def owned_and_walked(i, j):
            q0, nq, k0, nk = tile(i, j)
            return ((q0, nq), (k0, nk)) if kernel == "fwd" \
                else ((k0, nk), (q0, nq))

        def sub_tile(own_at, walked_at):
            """The rule's answer for the `_SUB` x `_SUB` sub-tile at these
            positions of the owned and of the walked axis."""
            q_at, k_at = (own_at, walked_at) if kernel == "fwd" \
                else (walked_at, own_at)
            return rule.tile(q_at + offset, _SUB, k_at, _SUB)

        def cut(i, j):
            """The kind that does this (masked) tile on fewer sub-tiles, or
            None; square, of several sub-tiles, asked of the rule sub-tile
            by sub-tile as the tile itself was. `DIAGONAL`: nothing is kept
            off the aligned diagonal sub-tiles. A `Triangle`: nothing is
            kept on one side of the sub-tile diagonal."""
            (own0, own), (walked0, wide) = owned_and_walked(i, j)
            if own != wide or own % _SUB or own == _SUB:
                return None
            subs = range(0, own, _SUB)
            kept = {b < a for a in subs for b in subs if a != b
                    and sub_tile(own0 + a, walked0 + b)[0]}
            if not kept:
                return DIAGONAL
            return Triangle(kept.pop()) if len(kept) == 1 else None

        def band(i, steps):
            """The `Band` step that does this grid row's whole-tile `steps`
            on fewer sub-tiles, or None: every group keeps ONE run of
            sub-tiles of the tiles' span, all as long, each a sub-tile on
            from the last, no longer than two steps (a step's scores
            [groups, _SUB, run * _SUB] have VMEM to fit)."""
            if rule is None or not steps or [j for j, _ in steps] != list(
                    range(steps[0][0], steps[-1][0] + 1)):
                return None
            (own0, own), (walked0, wide) = owned_and_walked(i, steps[0][0])
            if own % _SUB or own == _SUB or wide % _SUB or own % wide:
                return None
            runs = set()
            for a in range(0, own, _SUB):
                kept = [b for b in range(0, len(steps) * wide, _SUB)
                        if sub_tile(own0 + a, walked0 + b)[0]]
                if not kept or kept != list(
                        range(kept[0], kept[0] + len(kept) * _SUB, _SUB)):
                    return None
                runs.add((walked0 + kept[0] - a - own0, len(kept)))
            if len(runs) > 1:
                return None
            (shift, run), = runs
            now = sum(1 if m == DIAGONAL else wide // _SUB for _, m in steps)
            return Band(shift, run) if run < now and run * _SUB <= 2 * wide \
                else None

        kept = []
        for i in range(n_rows):
            steps = []
            for j in range(n_steps):
                q0, nq, k0, nk = tile(i, j)
                some, every = (True, True) if rule is None \
                    else rule.tile(q0 + offset, nq, k0, nk)
                if not some:
                    continue
                _, (walked0, wide) = owned_and_walked(i, j)
                masked = not (every and walked0 + wide <= length)
                if masked and rule is not None:
                    masked = cut(i, j) or masked
                steps.append((j, masked))
            kept.append(steps)
        skipped = n_rows * n_steps - sum(map(len, kept))
        measure, budget = _STATIC_BUDGET[kernel]

        def unrolled(rows):
            return measure(map(len, rows)) <= budget \
                and sum(map(len, rows)) <= _STATIC_STEPS

        def same_rows(rows):
            """The `SharedRows` of a plan too long to unroll, or None: its
            largest group of grid rows of one shape (the steps' indices
            from the row's own, and their kinds), if that is several rows,
            holds half the plan's steps or more, is one contiguous range of
            steps a row and fits the kernel's `_SHARED_BUDGET`."""
            stride, rest = divmod(owned_and_walked(0, 0)[0][1], width)
            if rest:
                return None
            groups = {}
            for i, steps in enumerate(rows):
                groups.setdefault(tuple(
                    (j - i * stride, m) for j, m in steps), []).append(i)
            steps, same = max(groups.items(),
                              key=lambda g: len(g[0]) * len(g[1]))
            if len(same) < 2 or not 0 < len(steps) <= _SHARED_BUDGET[kernel] \
                    or 2 * len(steps) * len(same) < sum(map(len, rows)) \
                    or [at for at, _ in steps] != list(range(
                        steps[0][0], steps[0][0] + len(steps))):
                return None
            # the tiles with no mask first, then those under it, whole
            return SharedRows(tuple(same), stride, tuple(sorted(
                ((at, bool(m)) for at, m in steps), key=lambda s: s[1])))

        banded = [[(steps[0][0], b)] if (b := band(i, steps)) else steps
                  for i, steps in enumerate(kept)]
        if unrolled(banded):
            kept = banded  # a band step is straight-line code
        static = unrolled(kept)
        shared = None if static else same_rows(kept)
        body, bodies = (), ()
        if not static:
            any_masked = any(m for steps in kept for _, m in steps)
            body = _LOOP_BODY[kernel]

            def looped(steps):
                """A row of the loop as it runs -> (its steps, its bodies of
                each size): every step masked if any of the plan is, but for
                the whole tiles that fill bodies with no mask, which lead."""
                lead = [j for j, m in steps if not m]
                counts, left = [], len(lead)
                for k in body:
                    counts.append(left // k)
                    left %= k
                lead = lead[:len(lead) - left]
                rest = [(j, any_masked) for j, _ in steps if j not in lead]
                return [(j, False) for j in lead] + rest, tuple(counts)

            kept, bodies = zip(*(
                (sorted((i * shared.stride + at, m)
                        for at, m in shared.steps), (0,) * len(body))
                if shared and i in shared.rows else looped(steps)
                for i, steps in enumerate(kept)))
            if not any(map(any, bodies)):
                body, bodies = (), ()
        by_row = tuple(map(tuple, kept))

        def tile_of(i, j, masked):
            if not isinstance(masked, Band):
                return tile(i, j) + (masked,)
            (own0, own), _ = owned_and_walked(i, j)
            owned = (own0, own)
            walked = (own0 + masked.shift, own - _SUB + masked.run * _SUB)
            return (owned + walked if kernel == "fwd" else walked + owned) \
                + (masked,)

        tiles = tuple(tile_of(i, j, masked)
                      for i, steps in enumerate(by_row) for j, masked in steps)
        masked = sum(bool(t[4]) for t in tiles)

        def executed(q0, nq, k0, nk, masked):
            if isinstance(masked, Band):  # each group its run alone
                return (nq if kernel == "fwd" else nk) * masked.run * _SUB
            if isinstance(masked, Triangle):  # its two halves' pieces
                return _SUB * _SUB * sum(
                    (hi - lo) * (last - first) for lo, hi, first, last
                    in _halves(nq // _SUB, masked.leading))
            # a diagonal step executes its sub-tiles alone
            return nq * (_SUB if masked == DIAGONAL else nk)

        return KernelSchedule(
            width, static, tiles, by_row, len(tiles) - masked, masked,
            sum(t[4] == DIAGONAL for t in tiles),
            sum(isinstance(t[4], Band) for t in tiles),
            sum(isinstance(t[4], Triangle) for t in tiles), skipped,
            sum(executed(*t) for t in tiles) / needed if needed
            else float("inf"), shared, body, bodies)

    w = _step_width(block_q, block_k, rule)
    keys = plan(
        "fwd", w, _cdiv(s_q, block_q), _cdiv(s_k, w),
        lambda qi, j: (qi * block_q, block_q, j * w, w), s_k)
    wq = _step_width(block_k, block_q, rule)
    queries = plan(
        "dkv", wq, _cdiv(s_k, block_k), _cdiv(s_q, wq),
        lambda kj, i: (i * wq, wq, kj * block_k, block_k), s_q)
    return {"fwd": keys, "dq": keys, "dkv": queries}


def block_schedule(s_q, s_k, block_q, block_k, causal):
    """The steps the three kernels execute at these (already clamped) block
    sizes under `causal` (True, False, or a mask rule) -> {"fwd":
    KernelSchedule, "dq": ..., "dkv": ...}; all static, shared by the
    wrappers below and by tests/test_ops.py.

    A grid row (a block of queries in the forward and dq, of keys in dk/dv)
    walks the other axis in steps; each step's tile is asked of the rule:
    nothing kept -> no step (skipped, not masked), everything kept and
    inside the sequence -> a step with no mask (no iota, compare or
    `where`), else a masked step. A row's steps need not be one contiguous
    range (a block-diffusion x_t row visits x_0 tiles 0..i, then x_t tile i).
    A masked square tile of n x n sub-tiles of 128 is asked of the rule
    once more, sub-tile by sub-tile: where nothing off the n diagonal ones
    is kept, an unrolled plan runs it as a DIAGONAL step, each 128-row
    group against its own 128 of the walked axis under the same predicate
    (1 / n of the tile's matmuls and exponentials; every score left out is
    one the mask sets to exactly 0). What decides is the rule's answer, not
    its type: `CAUSAL`'s diagonal tiles keep 10 of their 16 sub-tiles, so
    no causal plan has such a step. Such a tile is a `Triangle` instead:
    where the rule keeps nothing in the sub-tiles on one side of the sub-
    tile diagonal, an unrolled plan runs each half of the owned block's
    groups against the walked axis no further than the half reaches, under
    the predicate, and leaves out the quarter of the tile that holds
    nothing (12 of 16 sub-tiles at 512 x 512): a causal call's diagonal
    tiles, a block-diffusion call's x_0 and x_t -> x_0 diagonal tiles, a
    window's trailing tile (the other hand) where its row is no band. A
    grid row's steps TOGETHER are asked
    the same way: where every 128-row group of the owned block keeps one
    contiguous run of sub-tiles of the tiles' span, each group's as long
    and a sub-tile on from the group's before (a window's rows: the kept
    band runs along the diagonal), on fewer sub-tiles than the steps
    execute and no longer than two steps, an unrolled plan runs the row as
    ONE `Band` step, each group against its own run under the predicate;
    every score left out is again one the mask sets to exactly 0. `CAUSAL`
    groups keep runs of 1, 2, 3, ... sub-tiles that all start at 0 and a
    block-diffusion row's kept tiles are not one range, so neither has a
    band step; a plan too long to unroll with them keeps its whole tiles
    and runs them in loops, a grid row's steps from a table: the tiles the
    rule keeps whole FIRST, four a body of straight-line code with no mask,
    then two a body (`_LOOP_BODY`, PR 60: a loop's iterations overlap
    nothing, the steps of one body do), and what is left, the tiles it cuts
    among it, one an iteration under the mask (`CAUSAL` at S 8,192 in tiles
    of 512: 112 of a kernel's 136 steps in bodies; at S 16,384, 480 of 528;
    at S 32,768, 1,984 of 2,080; `EvaWindows(32768, 2048, 16)`, 160 of
    304). But for the rows of
    ONE SHAPE: where a loop plan's largest group of rows whose steps lie as
    far from the row's own block, kind for kind, is several rows, holds
    half the plan's steps or more, is one contiguous range a row and no
    longer than `_SHARED_BUDGET`, those rows run one branch of straight-
    line code between them, placed by the grid row (`SharedRows`, PR 58):
    the tiles the rule keeps whole with no mask, then the ones it cuts,
    whole under the predicate; the other rows run the loop. A window of
    several tiles has such rows (4,096 in tiles of 512 at S 16,384: 24 of
    32 rows are 7 tiles with no mask between the trailing tile and their
    own). No two causal rows are alike, and neither are most of a block-
    diffusion or an `EvaWindows` plan's: every row of theirs loops.

    `executed_over_needed` is scores executed over scores the rule keeps.
    Starting point (before PR 26): steps of block_q x block_k whatever the
    diagonal left of them, 512 x 1024 at S 2048: 6 steps of 512 x 1,024 a
    head where the causal half is 2.10 M scores, 1.5 in all three kernels.
    Steps under a rule are now at most square (`_step_width`), so causal
    with an owned block of B rows gives 1 + B / S as whole tiles, 1.25 at
    512 and S 2048 and 1.125 at S 4096, and with the diagonal tiles as
    triangles (PR 51) 1.125 and 1.0625 (the forward and dq at S 4096; its
    dk/dv is a loop); block diffusion at length 2,048, block 4 runs 24 of
    the 64 tiles of 512 x 512 for 4,202,496 kept scores, 1.497 as whole
    tiles, its 4 x_t diagonal tiles (2,048 kept scores each) as diagonal
    steps, 21 tiles' area, 1.310, and its 4 x_0 and 4 x_t -> x_0 diagonal
    tiles as triangles: 19 tiles' area, 1.185; `SlidingWindow(512)` at S
    8,192 walked 31 tiles for 4,063,488 kept scores, 2.0, and runs 15 band
    steps of 4 x [128, 640] (PR 48) and the first row's own tile as a
    triangle: 1.258; `SlidingWindow(4096)` at S 16,384 walks 252 tiles for
    58,722,304 kept scores, 1.125, 168 of them with no mask since 24 rows
    share a branch (PR 58) and 24 more in the 8 edge rows' bodies (PR 60).
    """
    return _block_schedule(s_q, s_k, block_q, block_k, _rule(causal))


def _count_steps(*plans):
    # Per lowering (each time a kernel is built), not per run: the share of
    # loop steps on the unmasked path is unmasked / (unmasked + masked).
    device_profiler.count("flash.steps_unmasked",
                          sum(p.steps_unmasked for p in plans))
    device_profiler.count("flash.steps_masked",
                          sum(p.steps_masked for p in plans))
    # of the masked ones: run on the tile's diagonal sub-tiles alone
    device_profiler.count("flash.steps_diagonal",
                          sum(p.steps_diagonal for p in plans))
    # and those that are a grid row's one `Band` step
    device_profiler.count("flash.steps_band",
                          sum(p.steps_band for p in plans))
    # and those run on the kept side of their tile's sub-tile diagonal
    device_profiler.count("flash.steps_triangle",
                          sum(p.steps_triangle for p in plans))
    device_profiler.count("flash.tiles_skipped",
                          sum(p.steps_skipped for p in plans))
    # of them all: those of a loop plan's rows that run ONE shared branch
    device_profiler.count("flash.steps_shared_row",
                          sum(p.steps_shared for p in plans))
    # and those that run inside a loop's body of several steps
    device_profiler.count("flash.steps_loop_body",
                          sum(p.steps_loop_body for p in plans))


def _count_fetches(qs, ks):
    # Per lowering, as `_count_steps`, of the forward and of dq, which hold a
    # head's whole K and V: the (batch, head) grid rows, and those of them
    # that fetch a K and V of their own (a group of query heads shares its KV
    # head's; the one rotary key of a call in parts is not counted).
    b, h = qs[0].shape[:2]
    device_profiler.count("flash.kv_head_fetches", b * ks[0].shape[1])
    device_profiler.count("flash.head_rows", b * h)


def _count_stats(*stats):
    # Per lowering, as `_count_steps`: the HBM bytes of the row statistics
    # (lse, delta) as the backward kernels take them. A [.., 1] column's last
    # dim pads to 128 lanes in the tiled layout; a row is its numbers.
    device_profiler.count("flash.bwd_stat_column_bytes", sum(
        x.size * 128 * x.dtype.itemsize for x in stats if x.shape[-1] == 1))
    device_profiler.count("flash.bwd_stat_row_bytes", sum(
        x.size * x.dtype.itemsize for x in stats if x.shape[-1] != 1))


def _run_row(plan, row, steps_ref, body, carry, finish, diagonal, band,
             part):
    """Run this grid row's steps from `carry`, then `finish(carry)`.
    `body(masked)` -> a step (index, carry) -> carry; `diagonal` such a
    step for the plan's `DIAGONAL` ones; `band(kind)` -> the carry after a
    row's ONE `Band` step, which starts from none; `part`: a half's share
    of a `Triangle` step (`_triangle`).

    The trip counts depend on the grid row, and Mosaic schedules nothing
    across the iterations of a loop: the MXU then waits out every step's
    vector work (the forward ran 1.87 ms at S 2048 so, PERF.md §6, PR 26).
    A static plan has one branch a grid row instead, its steps straight-
    line code in which one step's matmuls run under its neighbours'
    softmax (1.3 ms), each step masked only if it needs it, and on its
    diagonal sub-tiles alone where they hold all the rule keeps of it (a
    quarter of the work at 512) or on the kept side of its sub-tile
    diagonal (`_triangle`); a row that is a band of sub-tiles (under
    a window of 512 each 128-row group's 5 of the 8 its two tiles span) is
    ONE step over the band, the groups a batch as a diagonal step's are,
    and the forward rescales nothing for a second; rows of one kind of
    band share ONE branch, the band placed by the grid row (the window
    call's three kernels 9.67 -> 7.03 ms, 6.80 so; PERF.md §6, PR 48).
    Otherwise the row's steps come from `steps_ref`
    (`KernelSchedule.table`, in SMEM) and run in loops, and since Mosaic
    overlaps what ONE iteration holds, an iteration holds several steps: for
    each size of `plan.body` a loop of bodies of that many steps (the row's
    whole tiles, with no mask; ONE traced step a size, unrolled when the
    kernel is lowered, so the code is as long whatever the plan), then a
    loop of the steps left, one an iteration, each masked whole if any step
    of the plan is (`CAUSAL` at S 16,384, rows of up to 32 steps, the three
    kernels 60.3 -> 52.0 ms; `_LOOP_BODY`, PERF.md §6, PR 60; at rows of 4
    steps a second loop cost 5-8% and the mask 2%, PR 26). A loop plan's
    `shared` rows are one row placed by
    the grid row, as a band is: they run ONE branch of their steps unrolled,
    the step indices traced (`row * stride + offset`), each masked only if
    it needs it, and only the other rows the loop (the window call of 9
    steps a row, its three kernels 30.9 -> 24.9 ms; PERF.md §6, PR 58)."""
    from jax.experimental import pallas as pl

    if not plan.static:
        step = body(plan.steps_masked > 0)

        def loop():
            c, first = carry, 1 + len(plan.body)
            whole = body(False)   # what fills bodies needs no mask
            for at, k in enumerate(plan.body, 1):
                c = jax.lax.fori_loop(
                    0, steps_ref[row, at],
                    lambda i, c, k=k, first=first: jax.lax.fori_loop(
                        0, k, lambda t, c: whole(
                            steps_ref[row, first + i * k + t], c), c,
                        unroll=True), c)
                first = first + k * steps_ref[row, at]
            finish(jax.lax.fori_loop(
                0, steps_ref[row, 0],
                lambda t, c: step(steps_ref[row, t + first], c), c))

        if plan.shared is None:
            return loop()
        rows, stride, steps = plan.shared
        first = row * stride

        def shared():
            c = carry
            # a run of steps of one kind is traced ONCE and unrolled when
            # the kernel is lowered: a body a step, 11 a kernel, was 11.6 s
            # of the cell's set-up (PERF.md §6, PR 58)
            for masked, run in itertools.groupby(steps, lambda s: s[1]):
                one = body(masked)
                for at, n, apart in _progressions([at for at, _ in run]):
                    c = jax.lax.fori_loop(
                        0, n, lambda t, c, at=at, apart=apart: one(
                            first + at + t * apart, c), c, unroll=True)
            finish(c)

        jax.lax.cond(_among(row, rows), shared, loop)
        return

    def branch(mine):
        def run():
            if isinstance(mine[0][1], Band):
                return finish(band(mine[0][1]))
            c = carry
            for j, masked in mine:
                if isinstance(masked, Triangle):
                    c = _triangle(masked, j, c, part)
                else:
                    c = (diagonal if masked == DIAGONAL
                         else body(masked))(j, c)
            finish(c)
        return run

    # a band lies as far from its row's own block whichever the row: the
    # rows of one kind of band share a branch (a window's 15 at S 8,192)
    rows = {}
    for i, mine in enumerate(plan.rows):
        kind = mine[0][1]
        rows.setdefault(kind if isinstance(kind, Band) else i, []).append(i)
    for same in rows.values():
        pl.when(functools.reduce(jnp.logical_or, [row == i for i in same]))(
            branch(plan.rows[same[0]]))


def _progressions(xs):
    """`xs` cut into runs that each step by one difference, as (first,
    length, difference)."""
    out = []
    for x in xs:
        if out and out[-1][1] == 1:
            out[-1][1:] = 2, x - out[-1][0]
        elif out and x == out[-1][0] + out[-1][1] * out[-1][2]:
            out[-1][1] += 1
        else:
            out.append([x, 1, 1])
    return out


def _among(row, rows):
    """Whether `row` (traced) is one of `rows`: two compares a run of them,
    not one a row (24 compares are as much to trace as a step's body)."""
    return functools.reduce(jnp.logical_or, [
        (row >= lo) & (row < lo + n * apart) & ((row - lo) % apart == 0)
        if apart > 1 else (row >= lo) & (row < lo + n)
        for lo, n, apart in _progressions(rows)])


def _triangle(kind, j, carry, part):
    """`carry` (arrays of [owned block, ...]) after step j as a `Triangle`:
    each half of the block through `part(j, rows, first, cols, its carry)`,
    the half's `rows` after the `cols` positions of the walked axis that
    start `first` into the tile, under the predicate. Two pieces and not
    one a `_SUB`-row group, which would run the 10 sub-tiles that hold
    scores alone: on the chip (PERF.md §6, PR 51) the three kernels
    together take the same time either way (dq and dk/dv, which the vector
    units bound, 6-10% under whole tiles by groups and 3-9% by halves; the
    forward, which the MXU bounds, 0 and 3-5%: a matmul of 128 rows keeps
    it no less busy than one of 512), and every piece is traced, lowered
    and compiled again in each grid row's branch: by groups a causal call
    took 2.4x the parent's time to trace, 9 s of `train-joyai-1chip`'s
    set-up."""
    out = []
    n = jax.tree.leaves(carry)[0].shape[0] // _SUB
    for lo, hi, first, last in _halves(n, kind.leading):
        rows = slice(lo * _SUB, hi * _SUB)
        out.append(part(j, rows, first * _SUB, (last - first) * _SUB,
                        _rows(carry, rows)))
    return jax.tree.map(lambda *halves: jax.lax.concatenate(halves, 0), *out)


def _rows(x, rows):
    """`rows` of every array in `x` (`lax.slice_in_dim`: a piece is traced
    in every branch that runs it, and `x[rows]` costs several times the
    tracing)."""
    return jax.tree.map(
        lambda a: jax.lax.slice_in_dim(a, rows.start, rows.stop), x)


def _grouped(x):
    """[n * _SUB, ...] -> [n, _SUB, ...], of every array in `x`: the rows of
    an owned block as the n groups a diagonal or a band step runs, each
    against its OWN positions of the walked axis (the kernels' matmuls then
    take the groups as a batch). On the chip the batch beats n sub-steps on
    slices of the carry: the forward 3.14 ms for 3.97, with no diagonal
    step 3.39 (PERF.md §6, PR 38); a band step's groups as four sub-steps
    straight from the refs: the forward 2.32 ms for the batch's 1.96, dq
    and dk/dv within 5% either way (PERF.md §6, PR 48)."""
    return jax.tree.map(lambda a: a.reshape(-1, _SUB, *a.shape[1:]), x)


def _flat(x):
    """`_grouped`'s inverse."""
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), x)


def _span(own_pos):
    """How much of the walked axis a step reads whose owned block's
    positions are `own_pos`, one a score ([rows, cols]: cols; groups [n,
    _SUB, cols]: their runs overlap, each a sub-tile on from the last)."""
    groups = own_pos.shape[0] if own_pos.ndim == 3 else 1
    return (groups - 1) * _SUB + own_pos.shape[-1]


def _runs(x, n):
    """[(n - 1) * _SUB + cols, ...] -> [n, cols, ...], of every array in
    `x`: what `_span` read of the walked axis as each group's own run. A
    diagonal step's (cols = `_SUB`) do not overlap: `_grouped`."""
    def runs(a):
        cols = a.shape[0] - (n - 1) * _SUB
        if cols == _SUB:
            return _grouped(a)
        return jnp.stack([a[g * _SUB:g * _SUB + cols] for g in range(n)])

    return jax.tree.map(runs, x)


def _walked_pos(start, shape):
    """int32 `shape`: each score's position along the axis a step walks (the
    last), for a step that starts at `start`; a diagonal step's [n, _SUB,
    _SUB]: group g's positions start at `start + g * _SUB`."""
    pos = start + jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    if len(shape) == 3:
        pos = pos + _SUB * jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return pos


def _mm(a, b, dim_a, dim_b):
    """Contract dim `dim_a` of a's last two with `dim_b` of b's, float32
    out; a leading dim (a diagonal step's groups) is a batch."""
    lead = a.ndim - 2
    batch = tuple(range(lead))
    return jax.lax.dot_general(
        a, b, (((lead + dim_a,), (lead + dim_b,)), (batch, batch)),
        preferred_element_type=jnp.float32)


# q and k reach the kernels as PARTS, tuples of one array or two whose
# channels together are the channels a score contracts over: (q,) and (k,),
# or, from latent attention's projections, (q [H, D], q_rope [H, R]) and
# (k [H, D], ONE k_rope [R] for all heads). How many is known when a kernel
# is built, so a call with one part runs one contraction a score and nothing
# else; with two no [.., D + R] q and k are built in HBM, the rotary key is
# never copied to H heads (`_spec`), and on the 128-wide MXU the two
# contractions are the same two passes as one over D + R (PERF.md §6, PR 32).


def _loaded(refs, rows=None):
    """float32 of what each of the parts `refs` holds for this grid step,
    or of its `rows`."""
    return tuple((r[0, 0] if rows is None else r[0, 0, rows, :])
                 .astype(jnp.float32) for r in refs)


def _dot_parts(a, b):
    """[rows of a, rows of b]: the parts' contractions over their channels,
    summed."""
    return functools.reduce(
        jnp.add, (_mm(x, y, 1, 1) for x, y in zip(a, b)))


def _store_parts(ref, parts):
    """The block `ref` holds <- `parts`, side by side along its last dim."""
    at = 0
    for x in parts:
        ref[0, 0, :, at:at + x.shape[-1]] = x.astype(ref.dtype)
        at += x.shape[-1]


def _spec(x, heads, rows, walked):
    """BlockSpec(s) on a grid (batch, head of `heads`, i) over `x` [B, n, S,
    W], or over each of a tuple of parts: `rows` of the sequence, block i of
    it where the grid walks this operand (`walked`), else all of it (rows =
    the padded sequence). An operand with fewer heads than the grid is read
    at head h * n // heads: the KV head of a group of `heads // n` query
    heads, or (n = 1) the ONE rotary key every head reads. Its block index
    stands while the grid walks the group's heads and their row blocks, so
    it is fetched once a group, not once a grid head."""
    from jax.experimental import pallas as pl

    def one(x):
        n = x.shape[1]
        group = heads // n

        def index(b_, h_, i):
            # chosen here, not traced: an operand with the grid's heads, and
            # the one rotary key, lower to the index maps they always had
            head = 0 if n == 1 else h_ if group == 1 else h_ // group
            return b_, head, i if walked else 0, 0

        return pl.BlockSpec((1, 1, rows, x.shape[3]), index)

    return jax.tree.map(one, x)


# What a kernel may keep in VMEM unless it states a limit of its own: the
# compiler's scoped default, 16 MiB on the v5e (its refusal at S 16,384: "Ran
# out of memory in memory space vmem ... Scoped allocation with size 16.75M
# and limit 16.00M": one KV head's K and V whole, 2 x 4 MiB, in the
# pipeline's two buffers, beside 0.75M of tiles; v6e's default is larger,
# every generation's VMEM is 128 MiB). `_VMEM_WORKING` is what a kernel is
# reckoned to need beside its blocks when asking whether the default holds it
# (a step's float32 tiles of 512 x 512 and the carry; the compiler counts
# under 1 MiB), `_VMEM_ROOM` what a stated limit leaves for them.
_VMEM_DEFAULT = 16 * 2**20
_VMEM_WORKING = 4 * 2**20
_VMEM_ROOM = 16 * 2**20


def _in_vmem(block_shape):
    """Elements a block takes in VMEM, whose tiles are 128 lanes wide: a
    64-wide head's K takes a 128-wide one's room (the v5e compiler on the
    first call with a 64-wide head as its only part, [1, 32768, 8, 64] K and
    V: "Scoped allocation with size 33.00M and limit 32.25M", where the
    unpadded blocks were reckoned at 16.25M). A [rows, 1] column (the
    forward's lse block) is counted as it is: its padding, under 1 MiB a
    kernel, is in the working set's room, and the calls at 128-wide heads
    state the limits they always did. The backward's lse and delta are
    lane-dense blocks (`_stat_forms`), counted as any other."""
    *lead, lanes = block_shape
    if lanes > 1:
        lanes = -(-lanes // 128) * 128
    return math.prod(lead) * lanes


def _vmem_limit(specs, arrays):
    """The VMEM limit to state for a kernel whose blocks are `specs` of
    `arrays` (operands and results, shapes and dtypes), or None where the
    default holds them: every block twice (the pipeline fetches the next
    while the kernel reads one; the whole-sequence K and V, or q and dO, are
    blocks too) plus the working set. A limit is what the kernel MAY use,
    not what it takes: reckoned from the blocks, so a longer sequence states
    a larger one and a call the default holds states none and lowers as it
    always did (every call at S <= 8,192 of a cell: 8.5 MiB of blocks at
    [8192, 128] K and V)."""
    blocks = 2 * sum(
        _in_vmem(spec.block_shape) * jnp.dtype(x.dtype).itemsize
        for spec, x in zip(jax.tree.leaves(specs), jax.tree.leaves(arrays)))
    if blocks + _VMEM_WORKING <= _VMEM_DEFAULT:
        return None
    return blocks + _VMEM_ROOM


def _pallas_call(kernel, plan, in_specs, out_specs, out_shape, **kw):
    """`pl.pallas_call(kernel, in_specs=in_specs, ...)` -> the call on its
    operands; a plan too long to unroll hands the kernel its table of steps
    first, whole, in SMEM; a call whose blocks are past the default VMEM
    states its limit (`_vmem_limit`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(*operands):
        limit = _vmem_limit((in_specs, out_specs), (operands, out_shape))
        device_profiler.count("flash.kernels", 1)  # per lowering
        device_profiler.count("flash.kernels_vmem_stated",
                              int(limit is not None))
        stated = {} if limit is None else {
            "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}
        build = functools.partial(pl.pallas_call, out_specs=out_specs,
                                  out_shape=out_shape, **stated, **kw)
        if plan.static:
            return build(kernel, in_specs=in_specs)(*operands)

        @functools.wraps(kernel.func)
        def with_steps(steps_ref, *refs):
            return kernel(*refs, steps_ref=steps_ref)

        return build(
            with_steps, in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            + list(in_specs))(jnp.asarray(plan.table), *operands)

    return call


def _aligned(start, width):
    from jax.experimental import pallas as pl

    return start if isinstance(start, int) else pl.multiple_of(start, width)


def _lane_chunks(x, op):
    """Combine the 128-lane column chunks of `x` [..., rows, n * 128] with
    `op` -> [..., rows, 128] (narrower `x`: unchanged): elementwise work
    only; what is left to reduce across lanes is one vreg a row group."""
    if x.shape[-1] % 128:
        return x
    return functools.reduce(
        op, [x[..., c:c + 128] for c in range(0, x.shape[-1], 128)])


# --------------------------------------------------------------------------
# Pallas forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_refs, k_refs, v_ref, o_ref, lse_ref, *, scale, mask,
                block_q, plan, seq_q, seq_k, steps_ref=None):
    from jax.experimental import pallas as pl

    width = plan.width
    qi = pl.program_id(2)
    q = _loaded(q_refs)  # ([block_q, D],), or with [block_q, R]
    # Causal with s_q != s_k (decode-style): query i corresponds to key
    # position i + (seq_k - seq_q), matching the oracle's tril(k=s_k-s_q).
    causal_offset = seq_k - seq_q

    def own_pos(cols, rows=slice(0, block_q)):
        # of `rows` of the block: built, never sliced from a wider one (the
        # v5e's compiler aborts on a slice of a broadcast iota that spans
        # lane tiles: "Check failed: limits[i] <= dim(i)")
        return (qi * block_q + (causal_offset + rows.start)
                + jax.lax.broadcasted_iota(
                    jnp.int32, (rows.stop - rows.start, cols), 0))

    q_pos = own_pos(width)

    def attend(q, q_pos, start, masked, carry):
        """(o, m, l) of the queries `q` at `q_pos` ([.., keys a query]:
        its position, one a score) after the keys from `start` on, or with
        no `carry` of these keys alone; `_grouped` queries: each group after
        its own run of them, a sub-tile on from the last group's."""
        rows = pl.dslice(start, _span(q_pos))
        k_blk = _loaded(k_refs, rows)
        v_blk = v_ref[0, 0, rows, :].astype(jnp.float32)
        if q_pos.ndim == 3:
            k_blk, v_blk = _runs((k_blk, v_blk), q_pos.shape[0])
        s = _dot_parts(q, k_blk) * scale  # [queries, keys a query]
        if masked:
            k_pos = _walked_pos(start, q_pos.shape)
            # Mask padding rows of a partial final K block (manual
            # dslice reads clamp, duplicating real rows) and, when
            # causal, future positions.
            valid = k_pos < seq_k
            if mask is not None:
                valid = valid & mask.keep(q_pos, k_pos)
            s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.max(s, axis=-1, keepdims=True)
        if carry is not None:
            o, m, l = carry
            m_new = jnp.maximum(m, m_new)
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        if carry is None:  # nothing to rescale
            return _mm(p, v_blk, 1, 0), m_new, _lane_chunks(p, jnp.add)
        corr = jnp.exp(m - m_new)
        # l stays one partial sum a lane: summed across lanes once,
        # after the loops, not in every step
        l_new = l * corr + _lane_chunks(p, jnp.add)
        o_new = o * corr + _mm(p, v_blk, 1, 0)
        return o_new, m_new, l_new

    def step(masked):
        return lambda j, carry: attend(
            q, q_pos, _aligned(j * width, width), masked, carry)

    def diagonal(j, carry):
        return _flat(attend(
            _grouped(q), _grouped(q_pos[:, :_SUB]), j * width, True,
            _grouped(carry)))

    def band(kind):
        return _flat(attend(
            _grouped(q), _grouped(own_pos(kind.run * _SUB)),
            pl.multiple_of(qi * block_q + kind.shift, _SUB), True, None))

    def part(j, rows, first, cols, carry):
        return attend(_rows(q, rows), own_pos(cols, rows),
                      j * width + first, True, carry)

    o0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    # m and l as columns ([block_q, 1] and lane partials), not 1-D rows:
    # they broadcast along lanes with no relayout
    m0 = jnp.full((block_q, 1), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((block_q, width if width % 128 else 128),
                   dtype=jnp.float32)

    def finish(carry):
        o, m, l = carry
        l = jnp.maximum(jnp.sum(l, axis=-1, keepdims=True), 1e-20)
        o_ref[0, 0] = (o / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log(l)

    _run_row(plan, qi, steps_ref, step, (o0, m0, l0), finish, diagonal, band,
             part)


def _pad_seq(x, block):
    """`x` [B, H, S, W], or every array of a tuple, with S padded up to a
    multiple of `block`."""
    def pad(x):
        short = (-x.shape[2]) % block
        if short == 0:
            return x
        return jnp.pad(x, ((0, 0), (0, 0), (0, short), (0, 0)))

    return jax.tree.map(pad, x)


def _flash_fwd_pallas(qs, ks, v, mask, scale, block_q, block_k, interpret):
    b, h, s_q, _ = qs[0].shape
    s_k, d_v = v.shape[2], v.shape[3]
    plan = block_schedule(s_q, s_k, block_q, block_k, mask)["fwd"]
    _count_steps(plan)
    _count_fetches(qs, ks)
    # Pad to block multiples: dynamic_slice CLAMPS out-of-range starts, which
    # would silently shift the last partial block. The kernels mask padded
    # positions via the true seq_q/seq_k.
    qs = _pad_seq(qs, block_q)
    ks, v = _pad_seq((ks, v), plan.width)
    s_q_pad, s_k_pad = qs[0].shape[2], v.shape[2]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, mask=mask, block_q=block_q, plan=plan,
        seq_q=s_q, seq_k=s_k,
    )
    out_shape = [
        jax.ShapeDtypeStruct((b, h, s_q_pad, d_v), v.dtype),
        jax.ShapeDtypeStruct((b, h, s_q_pad, 1), jnp.float32),
    ]
    o, lse = _pallas_call(
        kernel, plan,
        [_spec(qs, h, block_q, True), _spec(ks, h, s_k_pad, False),
         _spec(v, h, s_k_pad, False)],
        grid=(b, h, s_q_pad // block_q),
        out_specs=_spec(out_shape, h, block_q, True),
        out_shape=out_shape,
        interpret=interpret,
    )(qs, ks, v)
    return o[:, :, :s_q], lse[:, :, :s_q]


# --------------------------------------------------------------------------
# Pallas backward
# --------------------------------------------------------------------------

def _stat_forms(lse, delta, block_q, width):
    """The backward pass's row statistics, [B, H, S] float32 each (one number
    a query), S padded up to whole blocks of queries, as its two kernels take
    them -> (dq's: ONE array [B, H, blocks, 2 x block_q / lanes, lanes], a
    block's own numbers a grid step, lse's runs of up to `_SUB` lanes and
    then delta's; dk/dv's: lse and delta [B, H, steps, width] each, a row a
    loop step, all of a head's a block (`width` is `block_q` or divides it:
    `_step_width`)). All are reshapes of the lane-dense arrays: 4 bytes a
    number in HBM and a lane of VMEM each, where a [B, H, S, 1] column's
    last dim pads to 128 lanes in the tiled layout, 512 bytes a number
    written and read (PERF.md section 6, PR 63)."""
    b, h, s = lse.shape
    lanes = min(block_q, _SUB)
    stats = [jnp.pad(x, ((0, 0), (0, 0), (0, -s % block_q)))
             for x in (lse, delta)]
    own = jnp.concatenate([x.reshape(b, h, -1, block_q // lanes, lanes)
                           for x in stats], axis=3)
    return own, tuple(x.reshape(b, h, -1, width) for x in stats)


def _owned_columns(ref):
    """(lse, delta) [block_q, 1] each: the numbers of a grid step's own block
    of the statistics `ref` holds ([1, 1, 1, 2 x block_q / lanes, lanes],
    `_stat_forms`), down a score tile's rows, where dq needs them: the block
    transposed ONCE, a run of lanes a column, and a statistic's columns one
    under the other. Once a grid step: dq 3.20 -> 3.25 ms a call at [4, 32,
    4096, 128] on the v5e, for 0.84 ms of column copies the call no longer
    makes; as two operands, each spread by a diagonal select and 64 lane
    reduces, 3.30; each transposed, 3.37 (PERF.md section 6, PR 63)."""
    spread = ref[0, 0, 0].T   # [lanes, 2 x runs]
    runs = spread.shape[1] // 2
    return tuple(
        jnp.concatenate([spread[:, r:r + 1] for r in range(at, at + runs)])
        for at in (0, runs))


def _bwd_dq_kernel(q_refs, k_refs, v_ref, do_ref, stats_ref, dq_ref, *,
                   scale, mask, block_q, plan, seq_q, seq_k, steps_ref=None):
    """dq_ref [block_q, D (+ R)]: the parts' gradients side by side. lse and
    delta come lane-dense in ONE block, the grid step's own
    (`_owned_columns`)."""
    from jax.experimental import pallas as pl

    width = plan.width
    qi = pl.program_id(2)
    q = _loaded(q_refs)
    do = do_ref[0, 0].astype(jnp.float32)
    lse, delta = _owned_columns(stats_ref)  # [block_q, 1] each
    causal_offset = seq_k - seq_q

    def own_pos(cols, rows=slice(0, block_q)):
        return (qi * block_q + (causal_offset + rows.start)
                + jax.lax.broadcasted_iota(
                    jnp.int32, (rows.stop - rows.start, cols), 0))

    q_pos = own_pos(width)

    def attend(q, do, lse, delta, q_pos, start, masked, dq):
        """dq of the queries `q` at `q_pos` (as in the forward) plus what
        the keys from `start` on give it; `_grouped` operands: each group's
        own run of them."""
        rows = pl.dslice(start, _span(q_pos))
        k_blk = _loaded(k_refs, rows)
        v_blk = v_ref[0, 0, rows, :].astype(jnp.float32)
        if q_pos.ndim == 3:
            k_blk, v_blk = _runs((k_blk, v_blk), q_pos.shape[0])
        s = _dot_parts(q, k_blk) * scale
        if masked:
            k_pos = _walked_pos(start, q_pos.shape)
            valid = k_pos < seq_k
            if mask is not None:
                valid = valid & mask.keep(q_pos, k_pos)
            s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        if masked:
            p = jnp.where(valid, p, 0.0)
        dp = _mm(do, v_blk, 1, 1)
        ds = p * (dp - delta) * scale
        return tuple(d + _mm(ds, x, 1, 0) for d, x in zip(dq, k_blk))

    def step(masked):
        return lambda j, dq: attend(
            q, do, lse, delta, q_pos, _aligned(j * width, width), masked, dq)

    def diagonal(j, dq):
        return _flat(attend(
            *_grouped((q, do, lse, delta, q_pos[:, :_SUB])), j * width,
            True, _grouped(dq)))

    def band(kind):
        return _flat(attend(
            *_grouped((q, do, lse, delta, own_pos(kind.run * _SUB))),
            pl.multiple_of(qi * block_q + kind.shift, _SUB), True,
            _grouped(dq0)))

    def part(j, rows, first, cols, dq):
        return attend(
            *_rows((q, do, lse, delta), rows), own_pos(cols, rows),
            j * width + first, True, dq)

    dq0 = tuple(map(jnp.zeros_like, q))
    _run_row(plan, qi, steps_ref, step, dq0,
             functools.partial(_store_parts, dq_ref), diagonal, band, part)


def _bwd_dkv_kernel(q_refs, k_refs, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, mask, block_k, plan,
                    seq_q, seq_k, steps_ref=None):
    """Works on the TRANSPOSED score tile, s^T = k q^T [block_k, width]:
    every product is then a plain or a last-dims-contracted matmul (dv +=
    p^T do, dp^T = v do^T, dk += ds^T q), where p^T do taken from an
    untransposed p has Mosaic transpose the whole tile first. lse and delta
    come as rows ([steps, width]) to broadcast down the tile. dk_ref
    [block_k, D (+ R)]: the parts' gradients side by side, of a shared KV
    head or rotary key THIS head's share, as dv_ref (the backward rule sums
    the heads')."""
    from jax.experimental import pallas as pl

    width = plan.width
    kj = pl.program_id(2)
    k_blk = _loaded(k_refs)  # ([block_k, D],), or with [block_k, R]
    v_blk = v_ref[0, 0].astype(jnp.float32)

    def own_pos(cols, rows=slice(0, block_k)):
        # the whole block's adds no op: a loop plan traces to the text it had
        return (kj * block_k + rows.start if rows.start else kj * block_k) \
            + jax.lax.broadcasted_iota(
                jnp.int32, (rows.stop - rows.start, cols), 0)

    k_pos = own_pos(width)
    causal_offset = seq_k - seq_q

    def attend(k_blk, v_blk, k_pos, start, stat, masked, carry):
        """(dk, dv) of the keys `k_blk` at `k_pos` ([.., queries a key]: its
        position, one a score) plus what the queries from `start` on give
        them, `stat(ref)` the [1, queries] of lse or delta that are theirs;
        `_grouped` keys: each group's own run of queries, a sub-tile on from
        the last group's."""
        dk, dv = carry
        rows = pl.dslice(start, _span(k_pos))
        q = _loaded(q_refs, rows)
        do = do_ref[0, 0, rows, :].astype(jnp.float32)
        lse, delta = stat(lse_ref), stat(delta_ref)
        if k_pos.ndim == 3:
            groups, _, run = k_pos.shape
            q, do = _runs((q, do), groups)
            # a group's lanes of the row: [n, 1, run]
            lse, delta = (jnp.stack(
                [x[:, a:a + run] for a in range(0, groups * _SUB, _SUB)])
                for x in (lse, delta))
        s = _dot_parts(k_blk, q) * scale  # [keys, queries a key]
        if masked:
            q_row = _walked_pos(start, k_pos.shape)
            # Mask padding rows of a partial final Q block; when causal,
            # also mask future keys relative to the offset-shifted query
            # positions.
            valid = q_row < seq_q
            if mask is not None:
                valid = valid & mask.keep(q_row + causal_offset, k_pos)
            s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        if masked:
            p = jnp.where(valid, p, 0.0)
        dv = dv + _mm(p, do, 1, 0)
        dp = _mm(v_blk, do, 1, 1)
        ds = p * (dp - delta) * scale
        return tuple(d + _mm(ds, x, 1, 0) for d, x in zip(dk, q)), dv

    def whole(i, k_blk, v_blk, k_pos, masked, carry):
        """`attend` for step i of the plan's width: one row of lse and
        delta."""
        return attend(k_blk, v_blk, k_pos, _aligned(i * width, width),
                      lambda ref: ref[0, 0, pl.dslice(i, 1), :], masked,
                      carry)

    def step(masked):
        return lambda i, carry: whole(i, k_blk, v_blk, k_pos, masked, carry)

    def diagonal(i, carry):
        return _flat(whole(
            i, *_grouped((k_blk, v_blk, k_pos[:, :_SUB])), True,
            _grouped(carry)))

    def band(kind):
        pos = _grouped(own_pos(kind.run * _SUB))
        # the band's queries, out of the rows of `width` they lie in: the
        # first is the grid row's (whole steps an owned block) plus `shift`'s
        first, lane = divmod(kind.shift, width)
        first = first + kj * (block_k // width)

        def stat(ref):
            return jnp.concatenate(
                [ref[0, 0, pl.dslice(first + r, 1), :]
                 for r in range(_cdiv(lane + _span(pos), width))],
                axis=1)[:, lane:lane + _span(pos)]

        return _flat(attend(
            *_grouped((k_blk, v_blk)), pos,
            pl.multiple_of(kj * block_k + kind.shift, _SUB), stat, True,
            _grouped(zero)))

    def part(i, rows, first, cols, carry):
        return attend(
            *_rows((k_blk, v_blk), rows), own_pos(cols, rows),
            i * width + first,
            lambda ref: ref[0, 0, pl.dslice(i, 1), first:first + cols],
            True, carry)

    def finish(carry):
        dk, dv = carry
        _store_parts(dk_ref, dk)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    zero = tuple(map(jnp.zeros_like, k_blk)), jnp.zeros_like(v_blk)
    _run_row(plan, kj, steps_ref, step, zero, finish, diagonal, band, part)


def _bwd_dq_pallas(qs, ks, v, do, stats, mask, scale, block_q, plan,
                   interpret):
    """stats: `_stat_forms`' first."""
    from jax.experimental import pallas as pl

    b, h, s_q, _ = do.shape
    s_k = v.shape[2]
    # Same padding rationale as the forward (dynamic_slice clamping).
    qs, do = _pad_seq((qs, do), block_q)
    ks, v = _pad_seq((ks, v), plan.width)
    s_q_pad, s_k_pad = do.shape[2], v.shape[2]
    kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, mask=mask, block_q=block_q,
        plan=plan, seq_q=s_q, seq_k=s_k,
    )
    out_shape = jax.ShapeDtypeStruct(
        (b, h, s_q_pad, sum(x.shape[3] for x in qs)), qs[0].dtype)
    dq = _pallas_call(
        kernel, plan,
        [_spec(qs, h, block_q, True), _spec(ks, h, s_k_pad, False),
         _spec(v, h, s_k_pad, False), _spec(do, h, block_q, True),
         pl.BlockSpec((1, 1, 1) + stats.shape[3:],
                      lambda b_, h_, i: (b_, h_, i, 0, 0))],
        grid=(b, h, s_q_pad // block_q),
        out_specs=_spec(out_shape, h, block_q, True),
        out_shape=out_shape,
        interpret=interpret,
    )(qs, ks, v, do, stats)
    return dq[:, :, :s_q]


def _bwd_dkv_pallas(qs, ks, v, do, lse, delta, mask, scale, block_k, plan,
                    interpret):
    """lse, delta: `_stat_forms`' rows, one a loop step, for the transposed
    tile (see the kernel)."""
    b, h, s_q, _ = do.shape
    s_k = v.shape[2]
    qs, do = _pad_seq((qs, do), plan.width)
    ks, v = _pad_seq((ks, v), block_k)
    s_q_pad, s_k_pad = do.shape[2], v.shape[2]
    kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, mask=mask, block_k=block_k,
        plan=plan, seq_q=s_q, seq_k=s_k,
    )
    out_shape = [
        jax.ShapeDtypeStruct(
            (b, h, s_k_pad, sum(x.shape[3] for x in ks)), ks[0].dtype),
        jax.ShapeDtypeStruct((b, h) + v.shape[2:], v.dtype),
    ]
    dk, dv = _pallas_call(
        kernel, plan,
        [_spec(qs, h, s_q_pad, False), _spec(ks, h, block_k, True),
         _spec(v, h, block_k, True), _spec(do, h, s_q_pad, False),
         _spec(lse, h, lse.shape[2], False),
         _spec(delta, h, delta.shape[2], False)],
        grid=(b, h, s_k_pad // block_k),
        out_specs=_spec(out_shape, h, block_k, True),
        out_shape=out_shape,
        interpret=interpret,
    )(qs, ks, v, do, lse, delta)
    return dk[:, :, :s_k], dv[:, :, :s_k]


def _flash_bwd_pallas(qs, ks, v, o, lse, do, mask, scale, block_q, block_k,
                      interpret):
    """lse [B, H, S], as the forward rule saved it -> dq [B, H, S, D (+ R)],
    dk likewise and dv, one a QUERY head (every head's share of the gradient
    of the KV head, or rotary key, it read)."""
    plans = block_schedule(do.shape[2], v.shape[2], block_q, block_k, mask)
    _count_steps(plans["dq"], plans["dkv"])
    _count_fetches(qs, ks)
    # one number a row from its producer to both kernels, never [.., 1]:
    # XLA's own reduce, which it forms in the output of the matmul that
    # makes `do` (the attention's output projection, backwards) where it can
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    own, (lse, delta) = _stat_forms(lse, delta, block_q,
                                    plans["dkv"].width)
    _count_stats(own, lse, delta)
    dq = _bwd_dq_pallas(qs, ks, v, do, own, mask, scale, block_q,
                        plans["dq"], interpret)
    dk, dv = _bwd_dkv_pallas(qs, ks, v, do, lse, delta, mask, scale, block_k,
                             plans["dkv"], interpret)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom_vjp wrapper
# --------------------------------------------------------------------------

# Names of the forward rule's residuals (`jax.ad_checkpoint.checkpoint_name`):
# a remat policy that saves them runs no second forward kernel in its
# backward pass; under one that does not they cost nothing. lse is kept as
# [B, H, S]: as [B, H, S, 1] its last dim pads to 128 lanes in the TPU's tiled
# layout, 128x the bytes; the backward rule hands it on as it is.
RESIDUAL_NAMES = ("flash.o", "flash.lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(qs, ks, v, mask, scale, block_q, block_k, interpret):
    """qs, ks: q and k in parts (above), (q,) and (k,) [B, H, S, D] or (q,
    q_rope [B, H, S, R]) and (k, k_rope [B, 1, S, R]); v [B, H, S, Dv] -> o
    [B, H, S, Dv]. k and v may have fewer heads than q, H / group of them
    (GQA): they are read, and saved for the backward pass, as they are."""
    return _flash_fwd_pallas(qs, ks, v, mask, scale, block_q, block_k,
                             interpret)[0]


def _flash_fwd_rule(qs, ks, v, mask, scale, block_q, block_k, interpret):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _flash_fwd_pallas(qs, ks, v, mask, scale, block_q, block_k,
                               interpret)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse[..., 0], RESIDUAL_NAMES[1])
    return o, (qs, ks, v, o, lse)


def _flash_bwd_rule(mask, scale, block_q, block_k, interpret, res, do):
    qs, ks, v, o, lse = res
    dq, dk, dv = _flash_bwd_pallas(
        qs, ks, v, o, lse, do, mask, scale, block_q, block_k, interpret)

    def shared(g, x):
        """g [B, H, S, W], a gradient one a query head, as the gradient of
        `x` [B, n, S, W]: a group of H / n heads read ONE KV head (n = 1:
        every head the ONE rotary key), so its gradient is their sum, in
        float32."""
        b, n, *rest = x.shape
        if n == g.shape[1]:
            return g
        if n == 1:
            total = jnp.sum(g, axis=1, keepdims=True, dtype=jnp.float32)
        else:  # [B, n, the group's heads, S, W]
            total = jnp.sum(g.reshape(b, n, -1, *rest), axis=2,
                            dtype=jnp.float32)
        return total.astype(x.dtype)

    def of_parts(g, parts):
        """g [B, H, S, D (+ R)] cut into the gradient of each part as it was
        given."""
        out, at = [], 0
        for x in parts:
            out.append(shared(g[..., at:at + x.shape[3]], x))
            at += x.shape[3]
        return tuple(out)

    return of_parts(dq, qs), of_parts(dk, ks), shared(dv, v)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _group(h, h_kv):
    """Query heads a KV head."""
    if h % h_kv != 0:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    return h // h_kv


def _repeat_heads(x, times, axis=1):
    """Every array of `x` with each head (`axis`) `times` times, side by
    side, so head h of the result is head h // times: what the ORACLE takes
    (a K and V a query head), and a tp mesh wider than the KV heads. The
    kernels never need it."""
    if times == 1:
        return x
    return jax.tree.map(lambda a: jnp.repeat(a, times, axis=axis), x)


def flash_attention(
    q, k, v,
    causal: bool = True,
    scale: Optional[float] = None,
    # Measured on a v5e at [4, 32, 2048, 128], causal, ms a call forward /
    # backward (PERF.md §6, PR 26; the schedule before it, 512x1024: 2.22 /
    # 4.98): 512x512 1.34 / 3.81, 512x1024 1.30 / 4.02 (the same forward:
    # causal steps are cut to block_q), 256x512 1.65 / 3.92, 1024x512 1.52 /
    # 3.95, 256x256 1.64 / 4.06; 1024x1024 exceeds VMEM in dk/dv. Clamped
    # to seq below.
    block_q: int = 512,
    block_k: int = 512,
    use_pallas: Optional[bool] = None,
    interpret: bool = False, q_rope=None, k_rope=None, mask=None,
):
    """Exact attention over [B, S, H, D] inputs. GQA: k and v may have fewer
    heads, H / group; the kernels read KV head h // group for query head h
    (fetched once a group) and the backward rule sums dk and dv over each
    group in float32, so K and V never exist at H heads in HBM. Only the
    oracle path repeats them.

    Which path runs is read off the platform, never off a failure: with
    `use_pallas=None`, `jax.default_backend() == "tpu"` lowers to the
    Pallas kernels above and any other backend runs the jax.numpy oracle
    (what the CPU test mesh uses; `interpret=True` runs the kernels in
    the Pallas interpreter instead). Nothing falls back from one to the
    other at run time: chip_smoke.py fails unless `tpu_custom_call` is
    in the lowered train step.

    `mask`: a static rule for which (query, key) pairs are kept, in place
    of `causal` (which is the rule `CAUSAL`): e.g. `BlockDiffusion(length,
    block)`, `SlidingWindow(window)`, or `EvaWindows(length, window, chunk)`
    over MORE keys than queries (`ops/eva.py`). The kernels skip the tiles it
    keeps nothing of, run those it keeps whole with no mask, and apply its
    predicate in the others, on a tile's diagonal sub-tiles alone where
    they hold all it keeps, on the sub-tiles on the kept side of a tile's
    sub-tile diagonal, or on a row's band of sub-tiles in one step
    (`block_schedule`); no dense mask is built on the TPU path.

    In parts (latent attention): with `q_rope` [B, S, H, R] and `k_rope`
    [B, S, 1, R], ONE rotary key a (batch, position) for all heads, a score
    is q . k + q_rope . k_rope over D + R channels (default scale
    (D + R) ** -0.5). The rotary key's gradient, [B, S, 1, R], is summed
    over heads by the backward rule, not by the caller: `_flash_in_parts`.

    Sequence limit (v5e, libtpu 0.0.34, tests/test_tpu_aot_compile.py):
    each kernel instance keeps the whole sequence's K and V of ONE KV head
    (forward, dq) or q and dO of one head (dk/dv) in VMEM, twice over (the
    pipeline's two buffers). Under the compiler's default of 16 MiB a kernel
    that holds: forward and backward compile at S 2048, 4096 and 8192 (8 MiB
    of K and V at D 128; the backward kernels take lse and delta lane-dense,
    dk/dv as rows [steps, width]: as [S, 1] columns the backward pass was
    refused at S 8192) and state nothing. Past it a call states its
    own limit, reckoned from its blocks (`_vmem_limit`): S 16,384 (16 MiB of
    K and V: refused at "16.75M of 16.00M" until PR 50) compiles and runs at
    33 MiB of the chip's 128, under `CAUSAL` and `SlidingWindow` alike, and
    S 32,768 asks 49 MiB, at D 64 too (a 64-wide head's block is padded to
    128 lanes in VMEM: `_in_vmem`). What holds the sequence after that: VMEM
    still, at about S 100,000 a head (a window row reads 4,608 of 16,384
    keys it holds: fetching K and V by the blocks a row's steps touch is
    what lifts it, PERF.md section 7), and before it HBM, where the
    forward's lse leaves the kernel as [B, H, S, 1] float32, padded to 128
    lanes (235 MB a call at 28 heads x 16,384; the one column left: the
    backward pass hands no statistic on as one, `_stat_forms`).
    """
    b, s_q, h, d = q.shape
    group = _group(h, k.shape[2])
    if scale is None:
        scale = (d + (0 if q_rope is None else q_rope.shape[-1])) ** -0.5
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and not interpret
    mask = _rule(causal, mask)
    if isinstance(mask, SlidingWindow):
        device_profiler.count("flash.window_calls", 1)  # per lowering
    if q_rope is not None:
        return _flash_in_parts(q, q_rope, k, k_rope, v, mask, scale,
                               block_q, block_k, use_pallas, interpret)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if use_pallas or interpret:
        block_q = _clamp_block(block_q, s_q)
        block_k = _clamp_block(block_k, k.shape[1])
        o = _flash_bhsd((qt,), (kt,), vt, mask, scale, block_q, block_k,
                        interpret)
    else:  # the oracle wants a K and V a query head
        o = _reference_attention(qt, *_repeat_heads((kt, vt), group), mask,
                                 scale)
    return o.transpose(0, 2, 1, 3)


def flash_attention_sharded(q, k, v, mesh, causal: bool = True, scale=None,
                            q_rope=None, k_rope=None, mask=None, **kw):
    """shard_map-wrapped flash attention for use inside a pjit-sharded model:
    GSPMD has no partitioning rule for a Pallas custom call, so without it
    XLA all-gathers q/k/v to every device and replicates the kernel. Batch
    rides ('dp','fsdp') and heads ride 'tp' explicitly; each shard runs the
    kernel on its local [B/dp·fsdp, S, H/tp, D] block. Under GQA the KV heads
    ride tp as they are where tp divides them: a shard's query heads are
    whole groups, in order, over its own KV heads. KV heads tp does not
    divide are repeated first, only as far as the fewest heads it does (2
    KV heads over tp 4: each twice), not to the query heads' count. A `mask`
    rule speaks of positions only, so every shard runs under it as it is.
    In parts (`flash_attention`): `_sharded_in_parts` below.
    """
    from jax.sharding import PartitionSpec as P
    h, h_kv = q.shape[2], k.shape[2]
    _group(h, h_kv)
    # incl. the inter-slice dcn axis: a replicated batch dim would
    # all-gather q/k/v across DCN before every attention call
    batch_axes = tuple(a for a in ("dcn", "dp", "fsdp")
                       if mesh.shape.get(a, 1) > 1)
    batch_div = 1
    for a in batch_axes:
        batch_div *= mesh.shape[a]
    if q.shape[0] % max(batch_div, 1) != 0:
        batch_axes = ()
    head_axis = "tp" if (mesh.shape.get("tp", 1) > 1
                         and h % mesh.shape["tp"] == 0) else None
    if head_axis:
        # fewer KV heads than tp shards: each as often as gives every shard
        # whole heads, the fewest that do (h is a multiple of both)
        k, v = _repeat_heads(
            (k, v), math.lcm(h_kv, mesh.shape["tp"]) // h_kv, axis=2)
    spec = P(batch_axes or None, None, head_axis, None)
    fn = functools.partial(flash_attention, causal=causal, scale=scale,
                           mask=mask, **kw)
    if q_rope is not None:
        return _sharded_in_parts(fn, mesh, spec, P(batch_axes or None),
                                 q, k, v, q_rope, k_rope)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _flash_in_parts(q, q_rope, k, k_rope, v, mask, scale, block_q, block_k,
                    use_pallas, interpret):
    """`flash_attention` with q and k in the parts latent attention's
    projections make: q, k [B, S, H, D], q_rope [B, S, H, R], k_rope
    [B, S, 1, R] (ONE rotary key a (batch, position), used by every head),
    v [B, S, H, Dv] -> o [B, S, H, Dv]. A score is q . k + q_rope . k_rope
    times `scale`: attention over [q | q_rope] and [k | k_rope copied to H
    heads], which is what the oracle path (off a TPU) builds and the kernels
    never do. The backward rule returns the gradient of each operand as
    given; the rotary key's, [B, S, 1, R], is the sum over heads and is
    summed HERE (`_flash_bwd_rule`), not by the caller."""
    if k_rope is None or k_rope.shape[2] != 1:
        raise ValueError("q_rope comes with ONE rotary key, [B, S, 1, R]")

    def t(x):
        return x.transpose(0, 2, 1, 3)

    if use_pallas or interpret:
        o = _flash_bhsd(
            (t(q), t(q_rope)), (t(k), t(k_rope)), t(v), mask, scale,
            _clamp_block(block_q, q.shape[1]),
            _clamp_block(block_k, k.shape[1]), interpret)
    else:
        k_rope = jnp.broadcast_to(k_rope, q_rope.shape)
        k, v = _repeat_heads((t(k), t(v)), q.shape[2] // k.shape[2])
        o = _reference_attention(
            t(jnp.concatenate([q, q_rope], axis=-1)),
            jnp.concatenate([k, t(k_rope)], axis=-1), v, mask, scale)
    return t(o)


def _sharded_in_parts(fn, mesh, spec, rope_spec, q, k, v, q_rope, k_rope):
    """`flash_attention_sharded`'s call with the rotary parts carried through
    the shard_map: `q_rope` sharded like q; the one rotary key has no head
    axis to put on tp, so every tp shard reads all of it (`rope_spec`, the
    batch axes alone) and shard_map's transpose sums its gradient over tp."""
    return jax.shard_map(
        lambda q, k, v, qr, kr: fn(q, k, v, q_rope=qr, k_rope=kr), mesh=mesh,
        in_specs=(spec, spec, spec, spec, rope_spec), out_specs=spec,
        check_vma=False,
    )(q, k, v, q_rope, k_rope)
