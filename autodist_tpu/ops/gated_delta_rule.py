"""The gated delta rule: a recurrent state carried from token to token.

The token mixer of a Gated DeltaNet layer (Yang et al. 2024, "Gated Delta
Networks", arXiv 2412.06464).  For one value head, ``q_t, k_t [Dk]`` (of
the key head it reads, already L2-normed and scaled), ``v_t [Dv]``, a decay
``g_t <= 0`` and a step ``beta_t`` in (0, 1), the DEFINITION is

    S_t = exp(g_t) S_{t-1}
    d_t = beta_t (v_t - S_t^T k_t)
    S_t = S_t + k_t d_t^T              S [Dk, Dv] float32, S_0 = 0
    o_t = S_t^T q_t

(:func:`recurrence`, token by token: what the tests hold everything else
to).  :func:`gated_delta_rule` computes it ``chunk`` tokens at a time
(section 3.3 of the paper).  With ``G_i`` the running sum of ``g`` inside
a chunk, ``D[i, j] = exp(G_i - G_j)`` for ``j <= i`` and 0 above the
diagonal, and ``S`` the state the chunk is entered with:

    A[i, j] = beta_i (k_i . k_j) D[i, j]  for j < i        T = (I + A)^-1
    W = T (beta exp(G) K)      U = T (beta V)              rows of d_t:
    V' = U - W S               O = (Q exp(G)) S + ((Q K^T) * D) V'
    S <- exp(G_C) S + (K exp(G_C - G))^T V'

since ``d_i + sum_{j<i} A[i, j] d_j = beta_i (v_i - exp(G_i) S^T k_i)``.
**What no state enters** (``D``, ``A``, ``T``, ``W``, ``U``, ``(Q K^T) *
D``, ``Q exp(G)``, ``K exp(G_C - G)``) does not depend on the chunk before:
a dozen small products a chunk, each waiting for the last.  **The scan**
over the chunks is what is left: four products a chunk, ``(Q exp(G)) S``,
``W S``, ``((Q K^T) * D) V'`` and ``(K exp(G_C - G))^T V'``.

**On a TPU** both are ONE Pallas kernel a direction, and nothing but q, k,
v, ``G``, ``beta`` and o crosses main memory.  The forward's (HLO name
``gdn_scan`` from the scope it is called under): one program a (sequence,
value head), a SEGMENT of ``segment`` chunks a grid step, the segments the
sequential grid axis, ``S`` in VMEM scratch (64 KB at 128 x 128) from the
first to the last.  A step reads its tiles of q, k (of the key head ``i //
r``), v and a row a pair of chunks of ``G`` (the running sum, which stays
outside: 1 MB a sequence) and ``beta``; forms in VMEM what no state enters
of ALL the segment's chunks at once (:func:`_state_free`: the chunks two by
two, a pair's ``[C, C]`` blocks side by side on the lanes and a pair a
batch of every product, so that a vector register is full at a chunk of 64
and the pairs' chains of products, each a dozen long, run beside each
other; ``T`` by :func:`_inverse_doubling`); then walks the chunks with the
four state products; writes ``O`` once and the state the segment is
entered with.  q, k, v and o are taken and handed a head's tokens one
after the other (``[B * H, T, D]``, tokens on the sublanes), the layout
``ops/gdn_conv.py`` writes and the gated norm reads: XLA copies nothing
around the calls.  The backward's (HLO name ``gdn_scan_bwd``, a scope
inside ``gdn_scan``; the whole is a ``jax.custom_vjp``) is one program a
(sequence, value head) whose grid walks the segments IN REVERSE from the
states the forward wrote, the state's cotangent ``dS [Dk, Dv]`` float32 in
scratch from segment to segment.  A step forms what no state enters of its
segment again (the same :func:`_state_free`), runs the chunks forward
keeping of each the entering state, ``V'``, ``W`` and ``K exp(G_C - G)``
in scratch (1.8 MB at 8 chunks of 64 x 128), walks them back with the
three products that ``dS`` waits for, and then, all chunks at once again:

    dV' = Aqk^T dO + Kd dS'    dQg = dO S^T     dAqk = dO V'^T
    dKd = V' dS'^T             dU = dV'         dW = -dV' S^T
    dgc = sum(S * dS')         dS = Qg^T dO + gc dS' - W^T dV'
    dT = dW (beta e^G K)^T + dU (beta V)^T      dA = -(T^T dT T^T)

(the scan's transpose, eight products rounded as the forward's; ``T``'s by
the inverse's own rule, two products at ``highest``, masked to the strict
lower triangle) and the elementwise lines that formed the blocks
transposed by hand to ``dq, dk`` (of this value head's reading of its key
head: the sum over a key head's value heads is XLA's), ``dv``, ``dG`` (its
running sum from the chunk's end back is XLA's) and ``dbeta``.  No
residual but the operands, o and the segments' states; nothing of a
segment goes to main memory.  A segment of 8 chunks of 64 is a sixteenth
of an 8,192-token sequence; the rule alone at the cell's shapes, one
sequence and layer on the chip (my chip runs, PR 49): forward 4.5 ms and
forward with backward 10.6 at 8, 4.8 and 11.1 at 4; 5.8 and 13.1 with a
chunk a batch and its blocks half a register wide; 9.1 and 20.2 with a
segment's chunks unrolled one after the other (their chains do not
interleave of themselves); 10.8 and 23.0 with XLA's batched lines around
two scan kernels (PR 48).

**Elsewhere** the plain form in ``jax.numpy``: :func:`_prepare` forms what
no state enters for all chunks of a segment at once by batched products
(``T`` by :func:`_inverse`), :func:`_scan_plain` is ``lax.scan`` over the
four products, and the backward is ``jax.vjp`` of a segment
(:func:`_segment`) under a reverse ``lax.scan``.  The two share no line
but :func:`recurrence`'s definition, and the tests hold the one to the
other.

Numbers.  ``exp`` is only ever formed of differences ``G_i - G_j`` with
``i >= j`` (masked BEFORE the ``exp``), of ``G_i`` and of ``G_C - G_i``,
all ``<= 0``: ``g`` may reach -20 a token, -1,300 a chunk, where
``exp(-G)`` would be ``inf``.  ``G``, every ``exp``, ``T`` and ``S`` are
float32; ``T`` by doublings (``A`` being strictly lower triangular: of the
whole block in the kernels, of 16-wide diagonal blocks joined by products
in :func:`_inverse`) with every product at ``highest``;
all other products take the default precision (on a TPU one bfloat16 pass
with float32 sums; in the kernel the operands are rounded to bfloat16
right before each product, as ``ops/flash_attention.py`` does, unless
jax's default precision asks for ``highest``).

Layout: ``q, k [B, T, Hk, Dk]``, ``v [B, T, Hv, Dv]``, ``g, beta [B, T,
Hv]`` with ``Hv % Hk == 0``: value heads ``j * (Hv // Hk) .. (j + 1) *
(Hv // Hk) - 1`` read key head ``j``.  Returns ``o [B, T, Hv, Dv]``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops import pallas_utils
from autodist_tpu.ops.flash_attention import (
    _NN,
    _NT,
    _dot,
    _product_operand,
)

_HIGHEST = lax.Precision.HIGHEST
#: The forward's output ``[B, T, Hv, Dv]`` and the states its segments are
#: entered with ``[B * Hv, segments, Dk, Dv]`` (float32) carry these names
#: (``ops/flash_attention.py: RESIDUAL_NAMES`` says what that is for): a
#: checkpoint that keeps them hands the backward what the forward made,
#: and the scan's forward is not run again.
RESIDUAL_NAMES = ("gated_delta_rule/o", "gated_delta_rule/states")
#: the scope the chunked form runs under: the Pallas call's HLO name, and
#: what every operation of it, forward and backward, carries in ``tf_op``
KERNEL_NAME = "gdn_scan"
#: the scope, inside it, of the backward's scan kernel: its HLO name
BWD_KERNEL_NAME = "gdn_scan_bwd"


def recurrence(q, k, v, g, beta):
    """The definition, token by token under ``lax.scan`` (float32)."""
    hk, hv = q.shape[2], v.shape[2]
    q, k = (jnp.repeat(x.astype(jnp.float32), hv // hk, axis=2)
            for x in (q, k))

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x                  # [B, Hv, D], [B, Hv]
        s = s * jnp.exp(g_t)[..., None, None]
        d = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", s, k_t, precision=_HIGHEST))
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HIGHEST)

    s0 = jnp.zeros((q.shape[0], hv, q.shape[-1], v.shape[-1]), jnp.float32)
    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
               for x in (q, k, v, g, beta))
    return jnp.moveaxis(lax.scan(token, s0, xs)[1], 0, 1).astype(v.dtype)


def flops_per_token(dk: int, dv: int, chunk: int) -> dict:
    """FLOPs a token and value head, forward: the recurrence as written
    (``exp(g) S``, ``S^T k``, ``k d^T`` added, ``S^T q``: 7 a state
    element) and what the kernels' chunked form at this ``chunk``
    performs, a product of ``[m, k] x [k, n]`` counted ``2 m k n`` whatever
    its precision (a value head's program forms its own ``K K^T`` and ``Q
    K^T``, ``T`` by :func:`_inverse_doubling`)."""
    c = chunk
    computed = (4 * c * dk                    # K K^T, Q K^T
                + _doubling_flops(c) / c      # T
                + 2 * c * dk + 2 * c * dv     # W, U
                + 4 * dk * dv                 # (Q exp(G)) S, W S
                + 2 * c * dv                  # ((Q K^T) * D) V'
                + 2 * dk * dv)                # (K exp(G_C - G))^T V'
    return {"recurrence": 7 * dk * dv, "computed": computed}


_DIAGONAL = 16      # edge of the blocks of ``T`` found by doublings


def _inverse(a):
    """``(I + A)^-1`` of strictly lower triangular ``a [.., C, C]``, float32
    with every product at ``highest``, by block-wise forward substitution:
    the ``_DIAGONAL``-wide diagonal blocks by the doublings ``(I - A)(I +
    A^2)(I + A^4)..`` (``A^16 = 0``), then joined two by two, ``[[T1, 0],
    [-T2 A21 T1, T2]]``.  Measured on the chip at the cell's shapes (PR 40;
    ms a sequence and layer, forward and backward of the whole rule): 39.5
    so, 46.8 by six doublings of the whole 64 x 64 block at ``highest``
    (45.2 at three passes, 39.1 at one), 42.7 by ``solve_triangular``; the
    results agree to the last digit shown."""
    c = a.shape[-1]
    if c & (c - 1):
        raise ValueError(f"chunk={c}: expected a power of two")
    if c > _DIAGONAL:
        h = c // 2
        t1, t2 = _inverse(a[..., :h, :h]), _inverse(a[..., h:, h:])
        below = -jnp.matmul(jnp.matmul(t2, a[..., h:, :h],
                                       precision=_HIGHEST), t1,
                            precision=_HIGHEST)
        return jnp.concatenate([
            jnp.concatenate([t1, jnp.zeros_like(t1)], axis=-1),
            jnp.concatenate([below, t2], axis=-1)], axis=-2)
    eye = jnp.eye(c, dtype=a.dtype)
    inv, power = eye - a, a
    for _ in range(max(int(math.log2(c)) - 1, 0)):
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=_HIGHEST)
    return inv


def _prepare(q, k, v, g, beta, chunk: int):
    """What no state enters, for all chunks at once (the plain form).
    Returns ``(qg, w, u, aqk, kd, gc)`` in the scan's layout ``[P, N, ..]``
    (``P = B * Hv`` programs, ``N`` chunks): ``qg, w, kd [P, N, C, Dk]``,
    ``u [P, N, C, Dv]``, ``aqk [P, N, C, C]``, ``gc [P, N]``."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, n, c = hv // hk, t // chunk, chunk
    f32 = jnp.float32
    # [B, Hk, 1 | r, N, C, ..]
    qc, kc = (x.astype(f32).reshape(b, n, c, hk, 1, dk).transpose(
        0, 3, 4, 1, 2, 5) for x in (q, k))
    vc = v.astype(f32).reshape(b, n, c, hk, r, dv).transpose(0, 3, 4, 1, 2, 5)
    gs, bc = (x.astype(f32).reshape(b, n, c, hk, r).transpose(0, 3, 4, 1, 2)
              for x in (g, beta))
    gs = jnp.cumsum(gs, axis=-1)                          # G [B,Hk,r,N,C]
    rows, cols = (lax.broadcasted_iota(jnp.int32, (c, c), i) for i in (0, 1))
    # masked BEFORE the exp: above the diagonal G_i - G_j is positive
    decay = jnp.exp(jnp.where(cols <= rows,
                              gs[..., :, None] - gs[..., None, :], -jnp.inf))
    kk = jnp.einsum("bhsnid,bhsnjd->bhsnij", kc, kc)
    qk = jnp.einsum("bhsnid,bhsnjd->bhsnij", qc, kc)
    a = jnp.where(cols < rows, bc[..., None] * kk * decay, 0.0)
    inv = _inverse(a)
    e_g = jnp.exp(gs)[..., None]
    w = jnp.matmul(inv, bc[..., None] * e_g * kc)
    u = jnp.matmul(inv, bc[..., None] * vc)
    kd = kc * jnp.exp(gs[..., -1:] - gs)[..., None]
    out = (qc * e_g, w, u, qk * decay, kd)
    return tuple(x.reshape((b * hv, n) + x.shape[-2:]) for x in out) + (
        jnp.exp(gs[..., -1]).reshape(b * hv, n),)


def _scan_plain(qg, w, u, aqk, kd, gc, s):
    """The scan in ``jax.numpy`` from the state ``s [P, Dk, Dv]``: ``(o
    [P, N, C, Dv], the state after the last chunk)``."""
    def one(s, x):
        qg_c, w_c, u_c, aqk_c, kd_c, gc_c = x
        vp = u_c - jnp.einsum("pik,pkv->piv", w_c, s)
        o = jnp.einsum("pik,pkv->piv", qg_c, s) \
            + jnp.einsum("pij,pjv->piv", aqk_c, vp)
        s = s * gc_c[:, None, None] + jnp.einsum("pik,piv->pkv", kd_c, vp)
        return s, o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (qg, w, u, aqk, kd, gc))
    s, o = lax.scan(one, s, xs)
    return jnp.moveaxis(o, 0, 1), s


# Inside the kernels a segment's chunks go two by two: a PAIR's tokens one
# after the other on the sublanes (``[2C, D]``: the tiles as they come),
# its two ``[C, C]`` blocks side by side on the lanes (``[C, 2C]``: at a
# chunk of 64 a whole tile of 128 lanes, where one block would leave half
# of every vector register empty), and every product a pair a batch: so
# many INDEPENDENT products issued one after the other (a chunk's own
# chain, a dozen small products each waiting for the last, is bound by
# their latency).
_BNN = (((2,), (1,)), ((0,), (0,)))     # a @ b
_BNT = (((2,), (2,)), ((0,), (0,)))     # a @ b^T


def _t(x):
    """The last two axes swapped."""
    return jnp.swapaxes(x, -1, -2)


def _lanes(c: int):
    """Of packed blocks ``[1, C, 2C]``: ``(rows, local, left)``, the row,
    the column inside its own block, and whether the lane is the first
    block's."""
    rows, cols = (lax.broadcasted_iota(jnp.int32, (1, c, 2 * c), i)
                  for i in (1, 2))
    return rows, jnp.where(cols < c, cols, cols - c), cols < c


def _pack(x):
    """``[M, 2C, 2C] -> [M, C, 2C]``: the two diagonal blocks of a pair's
    product side by side on the lanes (nothing moves: the second is in the
    second half of the lanes already)."""
    c = x.shape[-1] // 2
    return jnp.where(_lanes(c)[2], x[:, :c], x[:, c:])


def _blocks(xp):
    """:func:`_pack` undone: ``[M, C, 2C] -> [M, 2C, 2C]``, the two blocks
    on the diagonal and zeros beside them."""
    c = xp.shape[1]
    rows, cols = (lax.broadcasted_iota(jnp.int32, (1, 2 * c, 2 * c), i)
                  for i in (1, 2))
    return jnp.where((rows < c) == (cols < c),
                     jnp.concatenate([xp, xp], axis=1), 0.0)


def _halves(xp):
    """The sums over the lanes of each block of ``[M, C, 2C]``, the pair's
    tokens one after the other: ``[M, 2C, 1]``."""
    left = _lanes(xp.shape[1])[2]
    return jnp.concatenate(
        [jnp.sum(jnp.where(left, xp, 0.0), axis=2, keepdims=True),
         jnp.sum(jnp.where(left, 0.0, xp), axis=2, keepdims=True)], axis=1)


def _beside(column):
    """``[M, 2C, 1] -> [M, C, 2C]``: a pair's column, each chunk's half
    along the lanes of its own block."""
    c = column.shape[1] // 2
    return jnp.where(_lanes(c)[2], column[:, :c], column[:, c:])


def _row(column):
    """``[M, 2C, 1] -> [M, 1, 2C]``: a pair's column as the row it is
    handed out as (a vector of a chunk crosses main memory as a ROW: a
    ``[.., C, 1]`` array pads to 128 lanes there)."""
    rows, local, _ = _lanes(column.shape[1] // 2)
    return jnp.sum(jnp.where(rows == local, _beside(column), 0.0), axis=1,
                   keepdims=True)


def _column(row):
    """:func:`_row` undone: ``[M, 1, 2C] -> [M, 2C, 1]``."""
    rows, local, _ = _lanes(row.shape[-1] // 2)
    return _halves(jnp.where(rows == local, row, 0.0))


def _ends(c: int):
    """The last lane of each block of a row ``[1, 1, 2C]``."""
    lane = lax.broadcasted_iota(jnp.int32, (1, 1, 2 * c), 2)
    return lane == c - 1, lane == 2 * c - 1


def _highest(a, b):
    """``a [M, R, 2C]`` times the block-diagonal ``b [M, 2C, 2C]``, float32
    at ``highest``."""
    return _dot(a, b, _BNN, jnp.float32)


def _inverse_doubling(ap):
    """``(I + A)^-1`` of strictly lower triangular blocks, packed ``[M, C,
    2C]``, INSIDE a kernel, float32 with every product at ``highest``:
    with ``P = -A`` (``P^C = 0``) the product ``(I + P)(I + P^2)(I +
    P^4)..`` by doublings of the whole block, each turn ONE product ``[inv;
    P^m] P^m`` of the two stacked along the sublanes (``inv P^m`` to add to
    ``inv``, and ``P^2m``): ``2 log2(C) - 2`` products of ``[C, C]`` a
    block in ``log2(C)`` calls of the MXU a pair.  :func:`_inverse`'s
    16-wide blocks would be slices off the lanes' grid in a kernel; the
    tests hold this to it to 1e-6."""
    c = ap.shape[1]
    if c & (c - 1):
        raise ValueError(f"chunk={c}: expected a power of two")
    rows, local, _ = _lanes(c)
    p = -ap
    inv = jnp.where(rows == local, 1.0, p)
    if c > 2:
        p = _highest(p, _blocks(p))
        for _ in range(c.bit_length() - 3):
            both = _highest(jnp.concatenate([inv, p], axis=1), _blocks(p))
            inv, p = inv + both[:, :c], both[:, c:]
        inv = inv + _highest(inv, _blocks(p))
    return inv


def _doubling_flops(c: int) -> int:
    """Products of :func:`_inverse_doubling` on one ``[c, c]`` block, ``2
    m k n`` each."""
    return max(2 * (c.bit_length() - 1) - 2, 0) * 2 * c ** 3


class _Vectors(NamedTuple):
    """The decays of a kernel's ``M`` pairs of chunks.  A pair's tokens one
    after the other, ``[M, 2C, 1]``: ``beta``, ``e_g`` (``exp(G)``),
    ``e_d`` (``exp(G_C - G)``); ``gc``, two of ``[M, 1, 1]`` (``exp(G_C)``
    of each chunk of the pair).  Packed ``[M, C, 2C]``: ``beta_p`` (a
    token's along its row of its block), ``decay`` (``D``, zero above the
    diagonals); ``strict [1, C, 2C]``, true below the diagonals."""
    beta: jax.Array
    e_g: jax.Array
    e_d: jax.Array
    gc: tuple
    beta_p: jax.Array
    decay: jax.Array
    strict: jax.Array


def _vectors(g_row, b_row) -> _Vectors:
    """From the rows ``[M, 1, 2C]`` of ``G`` (the running sum inside each
    chunk) and ``beta``, a pair's two chunks side by side."""
    c = g_row.shape[-1] // 2
    rows, local, _ = _lanes(c)
    g, beta = _column(g_row), _column(b_row)
    # the same number in every lane, which a slice off one lane is not
    # (Mosaic broadcasts that along one axis alone)
    last = [jnp.sum(jnp.where(end, g_row, 0.0), axis=2, keepdims=True)
            for end in _ends(c)]                               # 2 x [M, 1, 1]
    ends = jnp.concatenate([jnp.broadcast_to(x, g[:, :c].shape)
                            for x in last], axis=1)            # [M, 2C, 1]
    # masked BEFORE the exp: above a diagonal G_i - G_j is positive
    decay = jnp.exp(jnp.where(local <= rows, _beside(g) - g_row, -jnp.inf))
    return _Vectors(beta, jnp.exp(g), jnp.exp(ends - g),
                    tuple(jnp.exp(x) for x in last), _beside(beta), decay,
                    local < rows)


def _state_free(q, k, v, d: _Vectors, operand):
    """What no state enters, of a kernel's ``M`` pairs of chunks at once
    (``q, k [M, 2C, Dk]``, ``v [M, 2C, Dv]``): ``(kdm, t, w, u, aqk)`` with
    ``kdm = (K K^T) * D`` below the diagonals and ``aqk = (Q K^T) * D``
    packed ``[M, C, 2C]`` (``A = beta kdm``), ``t = (I + A)^-1`` the two
    blocks on the diagonal of ``[M, 2C, 2C]``, and ``W [M, 2C, Dk]``, ``U
    [M, 2C, Dv]`` as the module's text has them."""
    dot = functools.partial(_dot, operand=operand)
    kdm = jnp.where(d.strict, _pack(dot(k, k, _BNT)) * d.decay, 0.0)
    t = _blocks(_inverse_doubling(d.beta_p * kdm))
    w = dot(t, (d.beta * d.e_g) * k, _BNN)
    u = dot(t, d.beta * v, _BNN)
    return kdm, t, w, u, _pack(dot(q, k, _BNT)) * d.decay


def _pairs(x, n: int):
    """A segment's tokens ``[N C, D]`` as its pairs of chunks ``[M, 2C,
    D]``; an odd segment's last chunk beside zeros."""
    c = x.shape[0] // n
    if n % 2:
        x = jnp.concatenate([x, jnp.zeros((c, x.shape[1]), x.dtype)], axis=0)
    return x.reshape((n + 1) // 2, 2 * c, x.shape[1])


def _tokens_of(x, n: int):
    """:func:`_pairs` undone: ``[M, 2C, D] -> [N C, D]``."""
    c = x.shape[1] // 2
    return x.reshape(x.shape[0] * 2 * c, x.shape[2])[:n * c]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, s_ref,
                qg_ref, w_ref, u_ref, kd_ref, aqk_ref, *, operand):
    """One SEGMENT of one (sequence, value head): refs ``q, k [1, L, Dk]``
    (of the key head), ``v, o [1, L, Dv]``, ``g, b [1, 1, M, 1, 2C]`` (a
    pair of chunks' ``G`` and ``beta`` a row), ``st [1, 1, Dk, Dv]`` (the
    state the segment is entered with); scratch ``s [Dk, Dv]`` float32 and
    the segment's blocks ``qg, w, kd [L, Dk]``, ``u [L, Dv]``, ``aqk [M,
    C, 2C]``.  First what no state enters of ALL the segment's chunks, a
    pair a batch of every product, then the four state products a chunk,
    in order."""
    c = g_ref.shape[-1] // 2
    n = q_ref.shape[1] // c
    dot = functools.partial(_dot, dims=_NN, operand=operand)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    q, k = _pairs(q_ref[0], n), _pairs(k_ref[0], n)
    d = _vectors(g_ref[0, 0], b_ref[0, 0])
    _, _, w, u, aqk_ref[...] = _state_free(q, k, _pairs(v_ref[0], n), d,
                                           operand)
    w_ref[...], u_ref[...] = _tokens_of(w, n), _tokens_of(u, n)
    qg_ref[...] = _tokens_of(q * d.e_g, n)
    kd_ref[...] = _tokens_of(k * d.e_d, n)
    s = s_ref[...]
    st_ref[0, 0] = s
    for h in range(n):
        at = slice(h * c, (h + 1) * c)
        half = slice(h % 2 * c, (h % 2 + 1) * c)
        vp = u_ref[at] - dot(w_ref[at], s)                     # [C, Dv]
        o_ref[0, at] = dot(qg_ref[at], s) + dot(aqk_ref[h // 2][:, half], vp)
        s = s * d.gc[h % 2][h // 2] + dot(kd_ref[at].T, vp)
    s_ref[...] = s


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, ds_ref, states_ref, vp_ref,
                w_ref, kd_ref, du_ref, dkd_ref, dgc_ref, *, operand):
    """One SEGMENT of one (sequence, value head), the segments in REVERSE:
    refs as :func:`_fwd_kernel`'s, ``s0 [1, 1, Dk, Dv]`` the state the
    forward entered the segment with, ``do`` and the cotangents ``dq, dk
    [1, L, Dk]`` (of this VALUE head's reading of its key head), ``dv [1,
    L, Dv]``, ``dg, db [1, 1, M, 1, 2C]`` (of ``G`` and ``beta``, rows);
    scratch ``ds [Dk, Dv]`` float32, the state's cotangent walking back
    from segment to segment, of every chunk of the segment the state it is
    entered with ``[N, Dk, Dv]`` and ``dgc [M, 2, 1]``, and over the
    segment's tokens ``V'``, ``W``, ``K exp(G_C - G)``, ``dU`` and
    ``dKd``.  What no state enters of all the chunks first (the forward
    kernel's own function); the chunks forward, two products each; the
    chunks back, three products each on the way of ``dS``; then, all
    chunks at once again, the rest of the scan's transpose and the
    transposes of the lines that formed the blocks, through ``T`` by the
    inverse's own rule ``dA = -(T^T dT T^T)`` at ``highest``."""
    c = g_ref.shape[-1] // 2
    n = q_ref.shape[1] // c
    dot = functools.partial(_dot, operand=operand)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    q, k, v, do = (_pairs(x[0], n) for x in (q_ref, k_ref, v_ref, do_ref))
    d = _vectors(g_ref[0, 0], b_ref[0, 0])
    kdm, t, w, u, aqk = _state_free(q, k, v, d, operand)
    tt = _t(t)
    w_ref[...], vp_ref[...] = _tokens_of(w, n), _tokens_of(u, n)
    kd_ref[...] = _tokens_of(k * d.e_d, n)
    # Aqk^T dO, the part of dV' that no state enters
    du_ref[...] = _tokens_of(dot(_t(_blocks(aqk)), do, _BNN), n)
    s = s0_ref[0, 0]
    for h in range(n):
        at = slice(h * c, (h + 1) * c)
        states_ref[h] = s
        vp = vp_ref[at] - dot(w_ref[at], s, _NN)       # U until now
        vp_ref[at] = vp
        s = s * d.gc[h % 2][h // 2] + dot(kd_ref[at].T, vp, _NN)
    ds = ds_ref[...]
    for h in reversed(range(n)):
        at = slice(h * c, (h + 1) * c)
        gc = d.gc[h % 2][h // 2]
        dvp = du_ref[at] + dot(kd_ref[at], ds, _NN)
        du_ref[at] = dvp
        dkd_ref[at] = dot(vp_ref[at], ds, _NT)
        dgc_ref[h // 2, h % 2:h % 2 + 1] = jnp.sum(
            jnp.sum(states_ref[h] * ds, axis=0, keepdims=True), axis=1,
            keepdims=True)
        e_g = d.e_g[h // 2, h % 2 * c:(h % 2 + 1) * c]
        ds = (dot((q_ref[0, at] * e_g).T, do_ref[0, at], _NN) + ds * gc
              - dot(w_ref[at].T, dvp, _NN))
    ds_ref[...] = ds

    # the rest of the scan's transpose, all chunks at once
    du, dkd, vp = (_pairs(x[...], n) for x in (du_ref, dkd_ref, vp_ref))
    s = states_ref[...]
    if n % 2:
        s = jnp.concatenate([s, jnp.zeros_like(s[:1])], axis=0)
    do_c, du_c = (x.reshape(s.shape[0], c, x.shape[-1]) for x in (do, du))
    dqg = dot(do_c, s, _BNT).reshape(q.shape)
    dw = -dot(du_c, s, _BNT).reshape(k.shape)
    daqk = _pack(dot(do, vp, _BNT))
    # W = T (beta e^G K), U = T (beta V), T = (I + A)^-1
    wr0 = k * d.e_g
    dt = _pack(dot(dw, d.beta * wr0, _BNT) + dot(du, d.beta * v, _BNT))
    dwr, dur = dot(tt, dw, _BNN), dot(tt, du, _BNN)
    da = jnp.where(d.strict, -_highest(_highest(_pack(tt), _blocks(dt)), tt),
                   0.0)
    # A = beta (K K^T) * D, Aqk = (Q K^T) * D: the elementwise lines
    moved = da * (d.beta_p * kdm) + daqk * aqk         # dD * D
    dkk, dqk = _blocks(da * d.beta_p * d.decay), _blocks(daqk * d.decay)
    dq_ref[0] = _tokens_of(dot(dqk, k, _BNN) + dqg * d.e_g, n)
    dk_ref[0] = _tokens_of(
        dot(dkk + _t(dkk), k, _BNN) + dot(_t(dqk), q, _BNN)
        + dwr * (d.beta * d.e_g) + dkd * d.e_d, n)
    dv_ref[0] = _tokens_of(dur * d.beta, n)

    def over_lanes(x):
        return jnp.sum(x, axis=2, keepdims=True)

    by_wr0, by_kd = over_lanes(dwr * wr0), over_lanes(dkd * k * d.e_d)
    dg = (_halves(moved) + d.beta * by_wr0 + over_lanes(dqg * q * d.e_g)
          - by_kd)
    at_end = dgc_ref[...] * jnp.concatenate(d.gc, axis=1) + jnp.concatenate(
        [jnp.sum(by_kd[:, e * c:(e + 1) * c], axis=1, keepdims=True)
         for e in (0, 1)], axis=1)                              # [M, 2, 1]
    dg_ref[0, 0] = (
        _row(dg) - jnp.sum(moved, axis=1, keepdims=True)
        + sum(jnp.where(end, at_end[:, e:e + 1], 0.0)
              for e, end in enumerate(_ends(c))))
    db_ref[0, 0] = _row(_halves(da * kdm) + by_wr0 + over_lanes(dur * v))


def _by_head(x):
    """``[B, T, H, D] -> [B * H, T, D]``: a head's tokens one after the
    other, as ``ops/gdn_conv.py`` writes q, k, v and the gated norm reads
    ``o`` (a change of layout XLA makes none of)."""
    b, t, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, t, d)


def _by_token(x, b: int):
    """:func:`_by_head` undone."""
    p, t, d = x.shape
    return jnp.swapaxes(x.reshape(b, p // b, t, d), 1, 2)


def _rows(x, chunk: int, segment: int):
    """``[B, T, Hv] -> [B * Hv, segments, segment, chunk]``: a chunk's
    vector a row."""
    b, t, hv = x.shape
    return jnp.moveaxis(x, 1, 2).reshape(b * hv, -1, segment, chunk)


def _paired(x):
    """``[P, segments, N, C] -> [P, segments, M, 1, 2C]``: the rows of a
    pair of chunks side by side, a tile of their own in a kernel; an odd
    segment's last beside zeros."""
    p, segments, n, c = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, n % 2), (0, 0)))
    return x.reshape(p, segments, (n + 1) // 2, 1, 2 * c)


def _running(x, back: bool = False):
    """The running sum along the last axis (``back``: from the end), as a
    product with a triangle of ones at ``highest``: the three bfloat16
    parts of a float32 times 1.0 are exact, so it is the float32 sum in
    another order; ``jnp.cumsum`` over rows that are tiles of their own is
    a ``reduce_window`` that took 0.24 ms a call of 1 MB (my chip run, PR
    49)."""
    c = x.shape[-1]
    ones = jnp.triu(jnp.ones((c, c), x.dtype))
    return jnp.matmul(x, ones.T if back else ones, precision=_HIGHEST)


def _vectors_of(g, beta, chunk: int, segment: int):
    """The kernels' ``(G, beta)``: ``G`` the running sum of ``g`` inside
    each chunk, float32."""
    g, beta = (_rows(x.astype(jnp.float32), chunk, segment)
               for x in (g, beta))
    return _paired(_running(g)), _paired(beta)


def _params(blocks: int, scratch: int):
    """``blocks`` bytes of a grid step's operands, each held twice, beside
    ``scratch`` bytes and as much again for the values of a segment's
    chunks."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=pallas_utils.vmem_limit(
            2 * blocks + 2 * scratch + (2 << 20)))


# Jitted: traced and lowered once a shape, however many layers call it
@functools.partial(jax.jit, static_argnames=("chunk", "segment", "interpret"))
def _forward_kernel(q, k, v, g, beta, *, chunk, segment, interpret):
    """``(o [B, T, Hv, Dv] float32, states [B * Hv, segments, Dk, Dv])``
    by the forward kernel, a segment a grid step."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r, c, n, f32 = hv // hk, chunk, segment, jnp.float32
    rows, segments, m = n * c, t // (n * c), (n + 1) // 2

    def tokens(d, heads=1):
        return pl.BlockSpec((1, rows, d), lambda i, j: (i // heads, j, 0))

    vector = pl.BlockSpec((1, 1, m, 1, 2 * c), lambda i, j: (i, j, 0, 0, 0))
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, operand=_product_operand(interpret)),
        out_shape=(jax.ShapeDtypeStruct((b * hv, t, dv), f32),
                   jax.ShapeDtypeStruct((b * hv, segments, dk, dv), f32)),
        grid=(b * hv, segments),
        in_specs=[tokens(dk, r), tokens(dk, r), tokens(dv), vector, vector],
        out_specs=(tokens(dv),
                   pl.BlockSpec((1, 1, dk, dv), lambda i, j: (i, j, 0, 0))),
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)] + [
            pltpu.VMEM((rows, d), f32) for d in (dk, dk, dv, dk)] + [
            pltpu.VMEM((m, c, 2 * c), f32)],
        compiler_params=_params(
            4 * (rows * (2 * dk + 2 * dv) + dk * dv),
            4 * rows * (3 * dk + dv + c)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*(_by_head(x.astype(f32)) for x in (q, k, v)),
      *_vectors_of(g, beta, c, n))
    return _by_token(o, b), states


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _backward_kernel(q, k, v, g, beta, states, do, *, chunk, interpret):
    """The five cotangents (float32, in the operands' shapes) by the
    backward kernel, a segment a grid step from the last to the first."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    segments = states.shape[1]
    r, c, f32 = hv // hk, chunk, jnp.float32
    n = t // (c * segments)
    rows, m = n * c, (n + 1) // 2

    def tokens(d, heads=1):
        return pl.BlockSpec((1, rows, d),
                            lambda i, j: (i // heads, segments - 1 - j, 0))

    def block(*shape):
        return pl.BlockSpec(
            (1, 1) + shape,
            lambda i, j: (i, segments - 1 - j) + (0,) * len(shape))

    def like(d):
        return jax.ShapeDtypeStruct((b * hv, t, d), f32)

    vectors = jax.ShapeDtypeStruct((b * hv, segments, m, 1, 2 * c), f32)
    with jax.named_scope(BWD_KERNEL_NAME):
        dq, dk_, dv_, dg, db = pl.pallas_call(
            functools.partial(_bwd_kernel,
                              operand=_product_operand(interpret)),
            out_shape=(like(dk), like(dk), like(dv), vectors, vectors),
            grid=(b * hv, segments),
            in_specs=[tokens(dk, r), tokens(dk, r), tokens(dv),
                      block(m, 1, 2 * c), block(m, 1, 2 * c),
                      block(dk, dv), tokens(dv)],
            out_specs=(tokens(dk), tokens(dk), tokens(dv),
                       block(m, 1, 2 * c), block(m, 1, 2 * c)),
            scratch_shapes=[pltpu.VMEM((dk, dv), f32),
                            pltpu.VMEM((n, dk, dv), f32)] + [
                pltpu.VMEM((rows, d), f32) for d in (dv, dk, dk, dv, dk)
            ] + [pltpu.VMEM((m, 2, 1), f32)],
            compiler_params=_params(
                4 * (rows * (4 * dk + 3 * dv) + dk * dv),
                4 * (dk * dv * (1 + n) + rows * (3 * dk + 2 * dv))),
            interpret=interpret,
            name=BWD_KERNEL_NAME,
        )(*(_by_head(x.astype(f32)) for x in (q, k, v)),
          *_vectors_of(g, beta, c, n), states, _by_head(do.astype(f32)))

    def keys(x):      # the value heads of a key head read the same q, k
        return jnp.swapaxes(x.reshape(b, hk, r, t, dk).sum(axis=2), 1, 2)

    def rows_of(x):       # ``_paired`` undone
        return x.reshape(b * hv, segments, 2 * m, c)[:, :, :n]

    def tokens_of(x):     # ``_rows`` undone
        return jnp.moveaxis(x.reshape(b, hv, t), 1, 2)

    # G is a running sum inside a chunk: g's cotangent is dG's from the end
    return (keys(dq), keys(dk_), _by_token(dv_, b),
            tokens_of(_running(rows_of(dg), back=True)),
            tokens_of(rows_of(db)))


def _tokens(o, b: int):
    """``[B * Hv, N, C, Dv] -> [B, T, Hv, Dv]``."""
    p, n, c, dv = o.shape
    return o.reshape(b, p // b, n * c, dv).transpose(0, 2, 1, 3)


def _segment(q, k, v, g, beta, s, chunk: int):
    """The plain chunked form over one stretch of tokens entered with the
    state ``s [B * Hv, Dk, Dv]``: ``(o [B, T, Hv, Dv], the state after
    it)``."""
    o, s = _scan_plain(*_prepare(q, k, v, g, beta, chunk), s)
    return _tokens(o, q.shape[0]), s


def _segments(x, count: int):
    """``[B, T, ..] -> [count, B, T / count, ..]``."""
    return jnp.moveaxis(
        x.reshape(x.shape[:1] + (count, -1) + x.shape[2:]), 1, 0)


def _joined(x):
    """:func:`_segments` undone."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[:1] + (-1,) + x.shape[3:])


def _forward(q, k, v, g, beta, chunk, segment, interpret):
    """``(o [B, T, Hv, Dv] float32, states [B * Hv, segments, Dk, Dv])``:
    ``interpret`` None is the plain form a segment at a time, else the
    kernel (under the Pallas interpreter where true)."""
    b, t = q.shape[:2]
    if interpret is not None:
        return _forward_kernel(q, k, v, g, beta, chunk=chunk,
                               segment=segment, interpret=interpret)
    s0 = jnp.zeros((b * v.shape[2], q.shape[-1], v.shape[-1]), jnp.float32)

    def one(s, x):
        o, s_out = _segment(*x, s, chunk)
        return s_out, (o, s)

    count = t // (chunk * segment)
    _, (o, states) = lax.scan(one, s0, tuple(
        _segments(x, count) for x in (q, k, v, g, beta)))
    return _joined(o), jnp.moveaxis(states, 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule(q, k, v, g, beta, chunk, segment, interpret):
    return _forward(q, k, v, g, beta, chunk, segment, interpret)[0]


def _rule_fwd(q, k, v, g, beta, chunk, segment, interpret):
    o, states = map(checkpoint_name,
                    _forward(q, k, v, g, beta, chunk, segment, interpret),
                    RESIDUAL_NAMES)
    return o, (q, k, v, g, beta, states)


def _rule_bwd(chunk, segment, interpret, res, do):
    """From the states the forward entered its segments with.  Where the
    forward was the kernel, the backward kernel; where it was plain
    (``interpret`` None), the segments in reverse under ``lax.scan``, each
    ``jax.vjp`` of the plain form, the cotangent of the state walking back
    through them."""
    *operands, states = res
    if interpret is not None:
        grads = _backward_kernel(*operands, states, do, chunk=chunk,
                                 interpret=interpret)
        return tuple(dx.astype(x.dtype) for dx, x in zip(grads, operands))
    count = states.shape[1]

    def one(ds, x):
        *xs, s_in, do_seg = x
        _, pull = jax.vjp(lambda *a: _segment(*a, chunk), *xs, s_in)
        *dxs, ds = pull((do_seg, ds))
        return ds, tuple(dxs)

    xs = tuple(_segments(x, count) for x in operands) + (
        jnp.moveaxis(states, 1, 0), _segments(do.astype(jnp.float32), count))
    _, grads = lax.scan(one, jnp.zeros_like(states[:, 0]), xs, reverse=True)
    return tuple(_joined(dx).astype(x.dtype)
                 for dx, x in zip(grads, operands))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64,
                     segment: int = 8, interpret: Optional[bool] = None):
    """``o [B, T, Hv, Dv]`` of the gated delta rule over ``q, k [B, T, Hk,
    Dk]`` (L2-normed, ``q`` scaled), ``v [B, T, Hv, Dv]``, ``g, beta [B,
    T, Hv]``; ``T % chunk == 0``, ``chunk`` a power of two.  ``segment``:
    chunks the backward takes at a time (the largest divisor of ``T /
    chunk`` no larger is taken).  ``interpret``: None is the forward's scan
    as the Pallas kernel on a TPU and as ``lax.scan`` elsewhere; True or
    False is the kernel, under the Pallas interpreter or compiled."""
    t, hk = q.shape[1:3]
    hv = v.shape[2]
    if t % chunk or hv % hk or k.shape != q.shape:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}: {t} "
                         f"tokens in chunks of {chunk}, {hv} value heads "
                         f"over {hk} key heads")
    if interpret is None and not pallas_utils.use_interpret():
        interpret = False
    n = t // chunk
    segment = max(d for d in range(1, min(segment, n) + 1) if n % d == 0)
    with jax.named_scope(KERNEL_NAME):
        return _rule(q, k, v, g, beta, chunk, segment,
                     interpret).astype(v.dtype)
