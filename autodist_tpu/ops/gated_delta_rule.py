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
It is cut in three.  **What no state enters** (``D``, ``A``, ``T``,
``W``, ``U``, ``(Q K^T) * D``, ``Q exp(G)``, ``K exp(G_C - G)``) is
formed for ALL chunks at once by ordinary batched products
(:func:`_prepare`): a chain of a dozen 64 x 64 products a chunk would
otherwise stand inside the sequential part.  **The scan** over the chunks
is what is left: four products a chunk, ``(Q exp(G)) S``, ``W S``, ``((Q
K^T) * D) V'`` and ``(K exp(G_C - G))^T V'``.  On a TPU the forward's is one
Pallas kernel (HLO name ``gdn_scan`` from the scope it is called under):
one program a (sequence, value head), the chunks the sequential grid
axis, ``S`` in VMEM scratch (64 KB at 128 x 128) from the first chunk to
the last, every operand read once, ``O`` written once, and ``S`` written
out where a SEGMENT of ``segment`` chunks begins.  Elsewhere it is
``lax.scan`` over the same three products in ``jax.numpy``
(:func:`_scan_plain`).  **The backward** (:func:`_rule_bwd`; the whole is
a ``jax.custom_vjp``) walks the segments in reverse from the states the
forward wrote, the state's cotangent carried from segment to segment;
nothing of it is wider than a segment (at 8 chunks of 64 a sixteenth of
an 8,192-token sequence: XLA keeps more of so small a segment's batched
lines in VMEM, and a step of the cell measured 628 ms at 8, 631 at 16
and 644 at 32, PR 41).  Where the forward's scan is the kernel a segment
is written out (:func:`_segment_bwd`): what no state enters formed again
for the segment; the scan's transpose ONE Pallas kernel (HLO name
``gdn_scan_bwd``, a scope inside ``gdn_scan``), one program a (sequence,
value head) over twice the segment's chunks, ``_CHUNKS_A_TURN`` a turn of
the grid, which first runs the segment forward keeping every chunk's
entering state and ``V'`` in VMEM scratch (0.75 MB at 8 chunks of 64 x
128), then walks the chunks back with ``dS [Dk, Dv]`` float32 in scratch,
entered with the next segment's and written out once at the segment's
head:

    dV' = Aqk^T dO + Kd dS'    dQg = dO S^T     dAqk = dO V'^T
    dKd = V' dS'^T             dU = dV'         dW = -dV' S^T
    dgc = sum(S * dS')         dS = Qg^T dO + gc dS' - W^T dV'

(two products a chunk forward, eight back, rounded as the forward
kernel's; no turn's residual goes to main memory); and the cotangents it
hands back pulled through :func:`_prepare`'s batched lines by ``jax.vjp``,
through ``T = (I + A)^-1`` by the inverse's own rule ``dA = -(T^T dT
T^T)``, two products a block at ``highest`` (:func:`_inverse_by_rule`),
not by autodiff through the doublings and the joins.  Where the forward
is the plain form (``interpret`` None off the TPU) a segment is the plain
form's own transpose (``jax.vjp`` of :func:`_segment`): the two backwards
share :func:`_prepare` and nothing else, and the tests hold the one to
the other.

Numbers.  ``exp`` is only ever formed of differences ``G_i - G_j`` with
``i >= j`` (masked BEFORE the ``exp``), of ``G_i`` and of ``G_C - G_i``,
all ``<= 0``: ``g`` may reach -20 a token, -1,300 a chunk, where
``exp(-G)`` would be ``inf``.  ``G``, every ``exp``, ``T`` and ``S`` are
float32; ``T`` by block-wise forward substitution (:func:`_inverse`: the
16-wide diagonal blocks by doublings, ``A`` being strictly lower
triangular, joined by products) with every product at ``highest``;
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
from typing import Optional

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


def flops_per_token(dk: int, dv: int, chunk: int, share: int) -> dict:
    """FLOPs a token and value head, forward: the recurrence as written
    (``exp(g) S``, ``S^T k``, ``k d^T`` added, ``S^T q``: 7 a state
    element) and what the chunked form at this ``chunk`` performs, a
    product of ``[m, k] x [k, n]`` counted ``2 m k n`` whatever its
    precision; ``share`` value heads share a key head's ``K K^T`` and ``Q
    K^T``."""
    c = chunk
    computed = (4 * c * dk / share            # K K^T, Q K^T
                + _inverse_flops(c) / c       # T
                + 2 * c * dk + 2 * c * dv     # W, U
                + 4 * dk * dv                 # (Q exp(G)) S, W S
                + 2 * c * dv                  # ((Q K^T) * D) V'
                + 2 * dk * dv)                # (K exp(G_C - G))^T V'
    return {"recurrence": 7 * dk * dv, "computed": computed}


_DIAGONAL = 16      # edge of the blocks of ``T`` found by doublings


def _inverse_flops(c: int) -> int:
    """Products of :func:`_inverse` on one ``[c, c]`` block, ``2 m k n``
    each."""
    if c > _DIAGONAL:
        h = c // 2
        return 2 * _inverse_flops(h) + 2 * 2 * h ** 3
    return max(2 * int(math.log2(c)) - 1, 0) * 2 * c ** 3


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


@jax.custom_vjp
def _inverse_by_rule(a):
    """:func:`_inverse` whose cotangent is the inverse's own rule, ``dA =
    -(T^T dT T^T)`` (two products a block at ``highest``), in place of
    autodiff walking the doublings and the joins back; what of it lies
    outside the strictly lower triangle is the caller's mask's to drop."""
    return _inverse(a)


def _inverse_fwd(a):
    t = _inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(jnp.matmul(tt, dt, precision=_HIGHEST), tt,
                        precision=_HIGHEST),)


_inverse_by_rule.defvjp(_inverse_fwd, _inverse_bwd)


def _prepare(q, k, v, g, beta, chunk: int, inverse=_inverse):
    """What no state enters, for all chunks at once.  Returns ``(qg, w, u,
    aqk, kd, gc)`` in the scan's layout ``[P, N, ..]`` (``P = B * Hv``
    programs, ``N`` chunks): ``qg, w, kd [P, N, C, Dk]``, ``u [P, N, C,
    Dv]``, ``aqk [P, N, C, C]``, ``gc [P, N]``.  ``inverse`` forms ``T``
    (the written-out backward hands :func:`_inverse_by_rule`)."""
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
    inv = inverse(a)
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


def _scan_kernel(qg_ref, w_ref, u_ref, aqk_ref, kd_ref, gc_ref, o_ref, st_ref,
                 s_ref, *, segment: int, operand):
    """One chunk of one (sequence, value head): refs ``qg, w, kd [C, Dk]``,
    ``u, o [C, Dv]``, ``aqk [C, C]``, ``gc [1, Dv]``, ``st [Dk, Dv]`` (the
    state this chunk's segment is entered with); scratch ``s [Dk, Dv]``
    float32."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    dot = functools.partial(_dot, dims=_NN, operand=operand)
    s = s_ref[...]

    @pl.when(j % segment == 0)
    def _():
        st_ref[0, 0] = s

    vp = u_ref[0, 0] - dot(w_ref[0, 0], s)                   # [C, Dv]
    o_ref[0, 0] = dot(qg_ref[0, 0], s) + dot(aqk_ref[0, 0], vp)
    s_ref[...] = s * gc_ref[0, 0] + dot(kd_ref[0, 0].T, vp)


def _scan_pallas(qg, w, u, aqk, kd, gc, segment: int, interpret: bool):
    """``(o [P, N, C, Dv], states [P, N / segment, Dk, Dv])``."""
    p, n, c, dk = qg.shape
    dv = u.shape[-1]

    def block(*shape, every=1):
        return pl.BlockSpec((1, 1) + shape,
                            lambda i, j: (i, j // every, 0, 0))

    return pl.pallas_call(
        functools.partial(_scan_kernel, segment=segment,
                          operand=_product_operand(interpret)),
        out_shape=(jax.ShapeDtypeStruct((p, n, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct((p, n // segment, dk, dv),
                                        jnp.float32)),
        grid=(p, n),
        in_specs=[block(c, dk), block(c, dk), block(c, dv), block(c, c),
                  block(c, dk), block(1, dv)],
        out_specs=(block(c, dv), block(dk, dv, every=segment)),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qg, w, u, aqk, kd,
      jnp.broadcast_to(gc[..., None, None], (p, n, 1, dv)))


def _scan_bwd_kernel(qg_ref, w_ref, u_ref, aqk_ref, kd_ref, gc_ref, s0_ref,
                     do_ref, dsn_ref, dqg_ref, dw_ref, du_ref, daqk_ref,
                     dkd_ref, dgc_ref, ds0_ref, s_ref, ds_ref, states_ref,
                     vp_ref, *, operand):
    """One turn of one (sequence, value head) over a segment of ``N``
    chunks, ``H`` chunks a turn, ``2 N / H`` turns: the first half run the
    segment forward from ``s0 [Dk, Dv]`` and keep every chunk's entering
    state and ``V'`` in scratch (``states [N, Dk, Dv]``, ``vp [N, C,
    Dv]``); the second half walk the chunks back, ``ds [Dk, Dv]`` float32
    in scratch from ``dsn`` (the cotangent of the state the segment
    leaves) to ``ds0`` (of the state it is entered with), written once.
    Refs of a turn's chunks as :func:`_scan_kernel`'s of one, ``do`` and
    the cotangents in their operands' shapes, ``dgc [1, Dv]`` the sums
    over ``Dk`` of ``S * dS'``."""
    j = pl.program_id(1)
    turns = pl.num_programs(1) // 2
    per = qg_ref.shape[1]
    dot = functools.partial(_dot, operand=operand)

    @pl.when(j == 0)
    def _():
        s_ref[...] = s0_ref[0]

    @pl.when(j < turns)
    def _():
        s = s_ref[...]
        for h in range(per):
            c = j * per + h
            states_ref[c] = s
            vp = u_ref[0, h] - dot(w_ref[0, h], s, _NN)
            vp_ref[c] = vp
            s = s * gc_ref[0, h] + dot(kd_ref[0, h].T, vp, _NN)
        s_ref[...] = s

    @pl.when(j == turns)
    def _():
        ds_ref[...] = dsn_ref[0]

    @pl.when(j >= turns)
    def _():
        ds = ds_ref[...]
        for h in reversed(range(per)):
            c = (2 * turns - 1 - j) * per + h
            s, vp, do = states_ref[c], vp_ref[c], do_ref[0, h]
            qg, w, kd = qg_ref[0, h], w_ref[0, h], kd_ref[0, h]
            dvp = dot(aqk_ref[0, h].T, do, _NN) + dot(kd, ds, _NN)
            dqg_ref[0, h] = dot(do, s, _NT)
            dw_ref[0, h] = -dot(dvp, s, _NT)
            du_ref[0, h] = dvp
            daqk_ref[0, h] = dot(do, vp, _NT)
            dkd_ref[0, h] = dot(vp, ds, _NT)
            dgc_ref[0, h] = jnp.sum(s * ds, axis=0, keepdims=True)
            ds = dot(qg.T, do, _NN) + ds * gc_ref[0, h] - dot(w.T, dvp, _NN)
        ds_ref[...] = ds

        @pl.when(j == 2 * turns - 1)
        def _():
            ds0_ref[0] = ds


#: chunks a turn of the backward kernel's grid takes (as many of them as
#: divide a segment): a segment of 32 alone on the chip took 1.45 ms at 1,
#: 1.16 at 2, 1.01 at 4, 0.96 at 8 and 0.94 at 16 (PR 41)
_CHUNKS_A_TURN = 4


def _scan_bwd_pallas(qg, w, u, aqk, kd, gc, s0, do, dsn, interpret: bool):
    """The scan's transpose over one segment: the cotangents of ``(qg, w,
    u, aqk, kd, gc)`` in their shapes and of ``s0 [P, Dk, Dv]``, from ``do
    [P, N, C, Dv]`` and ``dsn [P, Dk, Dv]``.  A block a forward turn does
    not read (or no turn writes yet) stays at the last chunks, the first
    the walk back takes: nothing is fetched or written back twice."""
    p, n, c, dk = qg.shape
    dv = u.shape[-1]
    f32 = jnp.float32
    per = math.gcd(n, _CHUNKS_A_TURN)
    turns = n // per

    def forth(i, j):     # turn j forward, then the last chunks
        return i, jnp.minimum(j, turns - 1), 0, 0

    def back(i, j):      # the last chunks until the walk back begins
        return i, jnp.minimum(turns - 1, 2 * turns - 1 - j), 0, 0

    def both(i, j):      # turn j forward, then the turns in reverse
        return i, jnp.minimum(j, 2 * turns - 1 - j), 0, 0

    def block(*shape, index=back):
        return pl.BlockSpec((1, per) + shape, index)

    def like(*arrays):
        return tuple(jax.ShapeDtypeStruct(x.shape, f32) for x in arrays)

    state = pl.BlockSpec((1, dk, dv), lambda i, j: (i, 0, 0))
    gc = jnp.broadcast_to(gc[..., None, None], (p, n, 1, dv))
    with jax.named_scope(BWD_KERNEL_NAME):
        *cotangents, dgc, ds0 = pl.pallas_call(
            functools.partial(_scan_bwd_kernel,
                              operand=_product_operand(interpret)),
            out_shape=like(qg, w, u, aqk, kd, gc, s0),
            grid=(p, 2 * turns),
            in_specs=[block(c, dk), block(c, dk, index=both),
                      block(c, dv, index=forth), block(c, c),
                      block(c, dk, index=both), block(1, dv, index=both),
                      state, block(c, dv), state],
            out_specs=(block(c, dk), block(c, dk), block(c, dv), block(c, c),
                       block(c, dk), block(1, dv), state),
            scratch_shapes=[pltpu.VMEM((dk, dv), f32),
                            pltpu.VMEM((dk, dv), f32),
                            pltpu.VMEM((n, dk, dv), f32),
                            pltpu.VMEM((n, c, dv), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(qg, w, u, aqk, kd, gc, s0, do, dsn)
    return (*cotangents, dgc.sum(axis=(-2, -1))), ds0


def _tokens(o, b: int):
    """``[B * Hv, N, C, Dv] -> [B, T, Hv, Dv]``."""
    p, n, c, dv = o.shape
    return o.reshape(b, p // b, n * c, dv).transpose(0, 2, 1, 3)


def _chunks(o, chunk: int):
    """:func:`_tokens` undone: ``[B, T, Hv, Dv] -> [B * Hv, N, C, Dv]``."""
    b, t, hv, dv = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b * hv, t // chunk, chunk, dv)


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
        o, states = _scan_pallas(*_prepare(q, k, v, g, beta, chunk),
                                 segment, interpret)
        return _tokens(o, b), states
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


def _segment_bwd(xs, s_in, do_seg, ds, chunk, interpret):
    """One segment's transpose: ``(the cotangents of its five operands, of
    the state it is entered with)``.  Where the forward was plain
    (``interpret`` None), ``jax.vjp`` of the plain form.  Where its scan
    was the kernel, written out: what no state enters formed again, the
    scan's transpose ONE kernel (:func:`_scan_bwd_pallas`), and the
    cotangents it hands back pulled through :func:`_prepare`'s few batched
    lines by ``jax.vjp``, through ``T`` by the inverse's own rule."""
    if interpret is None:
        _, pull = jax.vjp(lambda *a: _segment(*a, chunk), *xs, s_in)
        *dxs, ds = pull((do_seg, ds))
        return tuple(dxs), ds
    with jax.named_scope("prepare_again"):
        prepared, pull = jax.vjp(functools.partial(
            _prepare, chunk=chunk, inverse=_inverse_by_rule), *xs)
    cotangents, ds = _scan_bwd_pallas(*prepared, s_in,
                                      _chunks(do_seg, chunk), ds, interpret)
    with jax.named_scope("transposes"):
        return pull(cotangents), ds


def _rule_bwd(chunk, segment, interpret, res, do):
    """The segments in reverse from the states the forward entered them
    with, the cotangent of the state walking back through them."""
    *operands, states = res
    count = states.shape[1]

    def one(ds, x):
        *xs, s_in, do_seg = x
        dxs, ds = _segment_bwd(xs, s_in, do_seg, ds, chunk, interpret)
        return ds, dxs

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
