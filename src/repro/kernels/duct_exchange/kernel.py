"""Duct-ring Pallas TPU kernels.

``duct_window_kernel`` runs one window of the dense layout (apply the
staged sends, then drain) and ``duct_commit_kernel`` folds a W-fused
superstep's pushbuf into the rings; both are the engine's main path on
TPU.  Their ring state is laid out slot-major, ``(C, rings)``: rings lie
on the 128 lanes and slots on sublanes, so per-ring scalars are lane rows
that broadcast down the slots, the drained prefix is a sublane min over
blocked FIFO offsets, and every mask is an int32 compare against a slot
iota at full rank — no gathers, no boolean reshapes.  Payload words are
separate slot-major planes, ``(L, C, rings)``.  The grid is 1-D over lane
blocks of rings sized to a VMEM budget, and a ragged last block needs no
padding because no ring reads another.

``duct_exchange_kernel`` is the edge-major drain -> send pass with one ring
per tile row; no engine calls it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.duct_exchange.ops import dense_halo_select

_BLOCK_EDGES = 256

#: double-buffered VMEM bytes one grid step's blocks may take; v5e's
#: default scoped VMEM limit is 16 MiB, and the kernels' intermediates
#: need the rest
_VMEM_BUDGET = 4 * 1024 * 1024


def _duct_kernel(qa_ref, qt_ref, head_ref, size_ref,
                 rnow_ref, ract_ref, snow_ref, sact_ref, slat_ref, stouch_ref,
                 qa_out, qt_out, head_out, size_out,
                 drained_out, rtouch_out, pop_pos_out,
                 accepted_out, push_pos_out,
                 *, capacity: int, max_pops: int):
    qa = qa_ref[...]                 # (B, C) availability times
    qt = qt_ref[...]                 # (B, C) touch stamps
    head = head_ref[...]             # (B, 1)
    size = size_ref[...]             # (B, 1)
    rnow, ract = rnow_ref[...], ract_ref[...]
    snow, sact = snow_ref[...], sact_ref[...]
    slat, stouch = slat_ref[...], stouch_ref[...]
    B, C = qa.shape

    col = jax.lax.broadcasted_iota(jnp.int32, (B, C), dimension=1)
    off = (col - head) % C           # FIFO offset of every ring slot
    valid = off < size
    # --- drain: longest available FIFO prefix, head-blocking, bounded -----
    blocked = valid & (qa > rnow)
    blocked_off = jnp.min(jnp.where(blocked, off, C), axis=1, keepdims=True)
    d = jnp.minimum(jnp.minimum(blocked_off, size), max_pops)
    d = jnp.where(ract > 0, d, 0)
    popped = valid & (off < d)
    rtouch = jnp.sum(jnp.where(popped & (off == d - 1), qt, 0),
                     axis=1, keepdims=True)
    pop_pos = jnp.where(d > 0, (head + d - 1) % C, head)
    qa = jnp.where(popped, jnp.inf, qa)
    head2 = (head + d) % C
    size2 = size - d
    # --- send attempt: drop iff full, stamp latency-delayed availability --
    acc = (sact > 0) & (size2 < capacity)
    slot = (head2 + size2) % C
    at_slot = acc & (col == slot)
    qa = jnp.where(at_slot, snow + slat, qa)
    qt = jnp.where(at_slot, jnp.broadcast_to(stouch, (B, C)), qt)
    push_pos = jnp.where(acc, slot, 0)
    size3 = size2 + acc

    qa_out[...] = qa
    qt_out[...] = qt
    head_out[...] = head2
    size_out[...] = size3
    drained_out[...] = d
    rtouch_out[...] = rtouch
    pop_pos_out[...] = pop_pos
    accepted_out[...] = acc.astype(jnp.int32)
    push_pos_out[...] = push_pos


def _ring_block(R: int, bytes_per_ring: int) -> int:
    """Lane-block width over rings: the whole ring axis when it fits the
    VMEM budget, else the widest multiple of 128 lanes that does."""
    fit = max(128, _VMEM_BUDGET // max(bytes_per_ring, 1) // 128 * 128)
    return R if R <= fit else fit


def _row_bytes(*rows_and_bytes) -> int:
    """Double-buffered VMEM bytes per ring of a kernel's blocks: each
    ``(rows, itemsize)`` pair is one block, its sublane rows padded to 8."""
    return 2 * sum(-(-r // 8) * 8 * b for r, b in rows_and_bytes)


def _window_kernel(qa_ref, qt_ref, qp_ref, head_ref, size_ref,
                   ppos_ref, pacc_ref, pav_ref, ptch_ref, ppay_ref,
                   rnow_ref, ract_ref,
                   qa_out, qt_out, qp_out, head_out, size_out,
                   drained_out, rtouch_out, fpay_out,
                   *, max_pops: int):
    """Fused dense-layout window over a block of rings laid out on lanes:
    push-apply -> drain, one VMEM-resident read-modify-write sweep.

    Ring state is slot-major, ``(C, rings)``, so every per-ring scalar is
    a ``(1, rings)`` row that broadcasts down the slot sublanes and every
    mask is built at full rank from int32 compares against a slot iota.
    The push phase only applies sends the engine already accepted (the
    drop-iff-full decision and occupancy bump happened eagerly at stage
    time), so the whole window's ring-state HBM traffic is this pass.
    """
    qa = qa_ref[...]                 # (C, B) availability times
    qt = qt_ref[...]                 # (C, B) touch stamps
    head = head_ref[...]             # (1, B)
    size = size_ref[...]             # (1, B) — staged pushes already counted
    rnow, ract = rnow_ref[...], ract_ref[...]
    C = qa.shape[0]
    L = qp_ref.shape[0]

    slot = jax.lax.broadcasted_iota(jnp.int32, qa.shape, 0)
    # --- push: masked writes at the staged slots --------------------------
    at = slot == jnp.where(pacc_ref[...] > 0, ppos_ref[...], -1)
    qa = jnp.where(at, pav_ref[...], qa)
    qt = jnp.where(at, ptch_ref[...], qt)
    # --- drain: longest available FIFO prefix, head-blocking, bounded -----
    off = (slot - head) % C
    valid = off < size
    blocked = valid & (qa > rnow)
    blocked_off = jnp.min(jnp.where(blocked, off, C), axis=0, keepdims=True)
    dr = jnp.minimum(jnp.minimum(blocked_off, size), max_pops)
    dr = jnp.where(ract > 0, dr, 0)
    popped = valid & (off < dr)
    fresh = valid & (off == dr - 1)
    rtouch_out[...] = jnp.sum(jnp.where(fresh, qt, 0), axis=0, keepdims=True)
    for l in range(L):
        qp = jnp.where(at, ppay_ref[l:l + 1, :], qp_ref[l])
        qp_out[l] = qp
        fpay_out[l:l + 1, :] = jnp.sum(
            jnp.where(fresh, qp, jnp.zeros((), qp.dtype)),
            axis=0, keepdims=True)
    qa_out[...] = jnp.where(popped, jnp.inf, qa)
    qt_out[...] = qt
    head_out[...] = (head + dr) % C
    size_out[...] = size - dr
    drained_out[...] = dr


def _lanes(x, dtype, R):
    """A per-ring array as one ``(1, R)`` lane row."""
    return jnp.asarray(x, dtype).reshape(1, R)


def _slot_major(x, dtype, R, C):
    """``(..., C)`` ring state as slot-major ``(C, R)``."""
    return jnp.asarray(x, dtype).reshape(R, C).T


def _pay_major(x, R, C):
    """``(..., C, L)`` payloads as ``(L, C, R)``: one slot-major plane per
    payload word."""
    return jnp.transpose(x.reshape(R, C, x.shape[-1]), (2, 1, 0))


@functools.partial(jax.jit, static_argnames=("max_pops", "interpret"))
def duct_window_kernel(q_avail, q_touch, q_pay, head, size,
                       push_pos, push_acc, push_avail, push_touch, push_pay,
                       recv_now, recv_active,
                       *, max_pops: int, interpret: bool = False):
    """Fused window over all rings, then the per-receiver halo merge.
    Returns the same tuple layout as ``ops.WindowResult`` (halo_win as
    bool).

    The kernel is per-ring and gather-free; the halo merge, which combines
    the ``d`` rings of a receiver, runs on the kernel's narrow ``(n, d,
    L)`` freshest-payload output with the shared ``dense_halo_select``.
    """
    n, d, C = q_avail.shape
    L = q_pay.shape[-1]
    R = n * d
    pdt = q_pay.dtype
    isz = jnp.dtype(pdt).itemsize
    B = _ring_block(R, _row_bytes((C, 4), (C, 4), *[(C, isz)] * L,
                                  *[(1, 4)] * 10, (L, isz)) * 2)
    rcv = jnp.arange(R, dtype=jnp.int32) // d
    args = (_slot_major(q_avail, jnp.float32, R, C),
            _slot_major(q_touch, jnp.int32, R, C),
            _pay_major(q_pay, R, C),
            _lanes(head, jnp.int32, R), _lanes(size, jnp.int32, R),
            _lanes(push_pos, jnp.int32, R), _lanes(push_acc, jnp.int32, R),
            _lanes(push_avail, jnp.float32, R),
            _lanes(push_touch, jnp.int32, R),
            jnp.asarray(push_pay, pdt).reshape(R, L).T,
            # receiver -> ring broadcast as a gather: the interleaving
            # repeat lowers to an unrolled relayout copy on TPU
            _lanes(jnp.asarray(recv_now)[rcv], jnp.float32, R),
            _lanes(jnp.asarray(recv_active)[rcv], jnp.int32, R))

    ring = pl.BlockSpec((C, B), lambda i: (0, i))
    row = pl.BlockSpec((1, B), lambda i: (0, i))
    pay = pl.BlockSpec((L, C, B), lambda i: (0, 0, i))
    words = pl.BlockSpec((L, B), lambda i: (0, i))
    vec = jax.ShapeDtypeStruct((1, R), jnp.int32)
    qa2, qt2, qp2, head2, size2, drained, rtouch, fpay = pl.pallas_call(
        functools.partial(_window_kernel, max_pops=max_pops),
        grid=(pl.cdiv(R, B),),
        in_specs=[ring, ring, pay] + [row] * 6 + [words, row, row],
        out_specs=[ring, ring, pay, row, row, row, row, words],
        out_shape=[jax.ShapeDtypeStruct((C, R), jnp.float32),
                   jax.ShapeDtypeStruct((C, R), jnp.int32),
                   jax.ShapeDtypeStruct((L, C, R), pdt),
                   vec, vec, vec, vec,
                   jax.ShapeDtypeStruct((L, R), pdt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    drained = drained.reshape(n, d)
    hpay, hwin = dense_halo_select(drained > 0, fpay.T.reshape(n, d, L))
    return (qa2.T.reshape(n, d, C), qt2.T.reshape(n, d, C),
            jnp.transpose(qp2, (2, 1, 0)).reshape(n, d, C, L),
            head2.reshape(n, d), size2.reshape(n, d), drained,
            rtouch.reshape(n, d), hpay, hwin)


def _commit_kernel(qa_ref, qt_ref, qp_ref, head_ref, size0_ref, cnt_ref,
                   pav_ref, ptch_ref, ppay_ref,
                   qa_out, qt_out, qp_out):
    """Superstep commit: fold each ring's compact pushbuf (up to W staged
    pushes) into the base ring at the live-tail slots.  Gather-free: rings
    lie on lanes, and each push ``j`` is one masked select down the slot
    sublanes — dead (j >= cnt) pushes match no slot and leave the base
    values.
    """
    qa = qa_ref[...]                 # (C, B)
    qt = qt_ref[...]                 # (C, B)
    tail = head_ref[...] + size0_ref[...]   # (1, B)
    cnt = cnt_ref[...]               # (1, B)
    C = qa.shape[0]
    W = pav_ref.shape[0]
    L = qp_ref.shape[0]

    slot = jax.lax.broadcasted_iota(jnp.int32, qa.shape, 0)
    at = [slot == jnp.where(cnt > j, (tail + j) % C, -1) for j in range(W)]
    for j in range(W):
        qa = jnp.where(at[j], pav_ref[j:j + 1, :], qa)
        qt = jnp.where(at[j], ptch_ref[j:j + 1, :], qt)
    qa_out[...] = qa
    qt_out[...] = qt
    for l in range(L):
        qp = qp_ref[l]
        for j in range(W):
            qp = jnp.where(at[j], ppay_ref[l, j:j + 1, :], qp)
        qp_out[l] = qp


@functools.partial(jax.jit, static_argnames=("interpret",))
def duct_commit_kernel(q_avail, q_touch, q_pay, head, size0, pb_cnt,
                       pb_avail, pb_touch, pb_pay, *,
                       interpret: bool = False):
    """Fused superstep commit over all rings.  Returns the same tuple
    layout as ``ops.CommitResult``."""
    R, C = q_avail.shape
    W = pb_avail.shape[1]
    L = q_pay.shape[-1]
    pdt = q_pay.dtype
    isz = jnp.dtype(pdt).itemsize
    B = _ring_block(R, _row_bytes((C, 4), (C, 4), *[(C, isz)] * L,
                                  *[(1, 4)] * 3, (W, 4), (W, 4),
                                  *[(W, isz)] * L)
                    + _row_bytes((C, 4), (C, 4), *[(C, isz)] * L))
    args = (_slot_major(q_avail, jnp.float32, R, C),
            _slot_major(q_touch, jnp.int32, R, C),
            _pay_major(q_pay, R, C),
            _lanes(head, jnp.int32, R), _lanes(size0, jnp.int32, R),
            _lanes(pb_cnt, jnp.int32, R),
            jnp.asarray(pb_avail, jnp.float32).T,
            jnp.asarray(pb_touch, jnp.int32).T,
            jnp.transpose(jnp.asarray(pb_pay, pdt), (2, 1, 0)))

    ring = pl.BlockSpec((C, B), lambda i: (0, i))
    row = pl.BlockSpec((1, B), lambda i: (0, i))
    pay = pl.BlockSpec((L, C, B), lambda i: (0, 0, i))
    pushes = pl.BlockSpec((W, B), lambda i: (0, i))
    qa2, qt2, qp2 = pl.pallas_call(
        _commit_kernel,
        grid=(pl.cdiv(R, B),),
        in_specs=[ring, ring, pay, row, row, row, pushes, pushes,
                  pl.BlockSpec((L, W, B), lambda i: (0, 0, i))],
        out_specs=[ring, ring, pay],
        out_shape=[jax.ShapeDtypeStruct((C, R), jnp.float32),
                   jax.ShapeDtypeStruct((C, R), jnp.int32),
                   jax.ShapeDtypeStruct((L, C, R), pdt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    return qa2.T, qt2.T, jnp.transpose(qp2, (2, 1, 0))


@functools.partial(jax.jit,
                   static_argnames=("capacity", "max_pops", "interpret"))
def duct_exchange_kernel(q_avail, q_touch, head, size,
                         recv_now, recv_active,
                         send_now, send_active, send_lat, send_touch,
                         *, capacity: int, max_pops: int,
                         interpret: bool = False):
    """Fused drain→send over all edges.  Returns the same tuple layout as
    ``ops.ExchangeResult`` (accepted as bool)."""
    E, C = q_avail.shape
    B = min(_BLOCK_EDGES, E)
    pad = (-E) % B
    nb = (E + pad) // B

    def col1(x, dtype):
        x = jnp.asarray(x, dtype).reshape(E, 1)
        return jnp.pad(x, ((0, pad), (0, 0)))

    qa = jnp.pad(jnp.asarray(q_avail, jnp.float32), ((0, pad), (0, 0)))
    qt = jnp.pad(jnp.asarray(q_touch, jnp.int32), ((0, pad), (0, 0)))
    args = (qa, qt, col1(head, jnp.int32), col1(size, jnp.int32),
            col1(recv_now, jnp.float32), col1(recv_active, jnp.int32),
            col1(send_now, jnp.float32), col1(send_active, jnp.int32),
            col1(send_lat, jnp.float32), col1(send_touch, jnp.int32))

    ring = lambda i: (i, 0)  # noqa: E731 — shared index map
    ring_spec = lambda: pl.BlockSpec((B, C), ring)       # noqa: E731
    vec_spec = lambda: pl.BlockSpec((B, 1), ring)        # noqa: E731
    out = pl.pallas_call(
        functools.partial(_duct_kernel, capacity=capacity,
                          max_pops=max_pops),
        grid=(nb,),
        in_specs=[ring_spec(), ring_spec()] + [vec_spec()] * 8,
        out_specs=[ring_spec(), ring_spec()] + [vec_spec()] * 7,
        out_shape=[
            jax.ShapeDtypeStruct((E + pad, C), jnp.float32),
            jax.ShapeDtypeStruct((E + pad, C), jnp.int32),
        ] + [jax.ShapeDtypeStruct((E + pad, 1), jnp.int32)] * 7,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    qa2, qt2, head2, size2, drained, rtouch, pop_pos, acc, push_pos = out
    flat = lambda x: x[:E, 0]  # noqa: E731
    return (qa2[:E], qt2[:E], flat(head2), flat(size2), flat(drained),
            flat(rtouch), flat(pop_pos), flat(acc).astype(bool),
            flat(push_pos))
