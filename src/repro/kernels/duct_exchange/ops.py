"""Public duct-exchange wrappers: jnp twins + backend dispatch.

``duct_drain`` / ``duct_send`` are the two phases as pure-jnp functions —
the vectorized engine calls them separately around the application step
(drain feeds the halos the step consumes; the step's outputs feed the
send).  ``duct_exchange`` is the fused drain→send pass: the Pallas kernel
implements it in one VMEM-resident sweep on TPU, with the jnp composition
as the CPU/GPU path.  All three agree slot-for-slot with
``ref.duct_exchange_ref``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class DrainResult(NamedTuple):
    q_avail: jax.Array
    q_touch: jax.Array
    head: jax.Array
    size: jax.Array
    drained: jax.Array     # (E,) i32 messages popped
    recv_touch: jax.Array  # (E,) i32 touch of freshest popped (0 if none)
    pop_pos: jax.Array     # (E,) i32 ring slot of freshest popped


class SendResult(NamedTuple):
    q_avail: jax.Array
    q_touch: jax.Array
    size: jax.Array
    accepted: jax.Array    # (E,) bool — push accepted (False = dropped)
    push_pos: jax.Array    # (E,) i32 ring slot the push landed in


def duct_drain(q_avail, q_touch, head, size, recv_now, recv_active,
               *, max_pops: int, clear_popped: bool = True) -> DrainResult:
    """Bounded FIFO drain: pop while the head message is available.

    ``max_pops`` sequential pop attempts are unrolled; a pop chain stops at
    the first slot that is empty or not yet available (head-blocking, as in
    the event engine's ``Duct.latest``).

    ``clear_popped=False`` skips resetting popped availability slots to inf
    — a hot-loop optimization: slots outside ``[head, head+size)`` are
    never read, so only callers comparing raw ring state (parity tests)
    need the reset.
    """
    E, C = q_avail.shape
    rows = jnp.arange(E)
    drained = jnp.zeros(E, dtype=jnp.int32)
    alive = recv_active
    for i in range(max_pops):
        avail_i = q_avail[rows, (head + i) % C]
        can = alive & (i < size) & (avail_i <= recv_now)
        drained = drained + can
        alive = can
    delivered = drained > 0
    pop_pos = jnp.where(delivered, (head + drained - 1) % C,
                        head).astype(jnp.int32)
    recv_touch = jnp.where(delivered, q_touch[rows, pop_pos], 0)
    if clear_popped:
        off = (jnp.arange(C)[None, :] - head[:, None]) % C
        q_avail = jnp.where(off < drained[:, None], jnp.inf, q_avail)
    return DrainResult(q_avail, q_touch, (head + drained) % C,
                       size - drained, drained, recv_touch, pop_pos)


def duct_send(q_avail, q_touch, head, size,
              send_now, send_active, send_lat, send_touch,
              *, capacity: int) -> SendResult:
    """Best-effort push: drop iff the buffer is full; stamp latency."""
    E, C = q_avail.shape
    rows = jnp.arange(E)
    accepted = send_active & (size < capacity)
    pos = (head + size) % C
    # drop-mode scatter: rejected rows index out of bounds instead of
    # gathering old values for a where()
    safe_rows = jnp.where(accepted, rows, E)
    q_avail = q_avail.at[safe_rows, pos].set(send_now + send_lat,
                                             mode="drop")
    q_touch = q_touch.at[safe_rows, pos].set(send_touch, mode="drop")
    push_pos = jnp.where(accepted, pos, 0).astype(jnp.int32)
    return SendResult(q_avail, q_touch, size + accepted, accepted, push_pos)


class ExchangeResult(NamedTuple):
    q_avail: jax.Array
    q_touch: jax.Array
    head: jax.Array
    size: jax.Array
    drained: jax.Array
    recv_touch: jax.Array
    pop_pos: jax.Array
    accepted: jax.Array
    push_pos: jax.Array


def duct_exchange_jnp(q_avail, q_touch, head, size,
                      recv_now, recv_active,
                      send_now, send_active, send_lat, send_touch,
                      *, capacity: int, max_pops: int) -> ExchangeResult:
    """Fused drain→send as the composition of the two jnp phases."""
    d = duct_drain(q_avail, q_touch, head, size, recv_now, recv_active,
                   max_pops=max_pops)
    s = duct_send(d.q_avail, d.q_touch, d.head, d.size,
                  send_now, send_active, send_lat, send_touch,
                  capacity=capacity)
    return ExchangeResult(s.q_avail, s.q_touch, d.head, s.size, d.drained,
                          d.recv_touch, d.pop_pos, s.accepted, s.push_pos)


# ---------------------------------------------------------------------------
# Fused dense-layout window megakernel (DESIGN.md §10)
# ---------------------------------------------------------------------------
class WindowResult(NamedTuple):
    q_avail: jax.Array     # (n, d, C)
    q_touch: jax.Array     # (n, d, C)
    q_pay: jax.Array       # (n, d, C, L)
    head: jax.Array        # (n, d)
    size: jax.Array        # (n, d)
    drained: jax.Array     # (n, d) i32 messages popped
    recv_touch: jax.Array  # (n, d) i32 touch of freshest popped (0 if none)
    halo_pay: jax.Array    # (n, 4, L) freshest payload per halo slot
    halo_win: jax.Array    # (n, 4) bool: slot refreshed this window


def dense_halo_select(delivered, payload):
    """Per-receiver halo merge for the dense layout: slot ``s`` takes the
    payload of the highest delivering row ``j`` with ``j % 4 == s``.

    Rows are in sorted-source order, which for a fixed receiver is
    canonical-edge-id order, so "highest j wins" reproduces the edge-major
    path's segment_max tie-break as a d-step unrolled select — no scatter.
    ``delivered``: (n, d) bool; ``payload``: (n, d, L).  Returns
    ``(halo_pay (n, 4, L), halo_win (n, 4))``.
    """
    n, d = delivered.shape
    L = payload.shape[-1]
    pay_cols, win_cols = [], []
    for s in range(4):
        pay_s = jnp.zeros((n, L), payload.dtype)
        win_s = jnp.zeros((n,), bool)
        for j in range(s, d, 4):
            pay_s = jnp.where(delivered[:, j, None], payload[:, j], pay_s)
            win_s = win_s | delivered[:, j]
        pay_cols.append(pay_s)
        win_cols.append(win_s)
    return jnp.stack(pay_cols, axis=1), jnp.stack(win_cols, axis=1)


def dense_stage(head, size, active, *, capacity: int):
    """Eager stage decision for the dense layout: drop iff the ring is
    full *now*, against post-drain occupancy — the same judgement
    ``duct_send`` makes on the edge-major path, made one window early so
    the ring writes can ride into the next fused ``duct_window`` pass.
    Returns ``(pos, accepted)``: the slot each accepted push will land in
    and the per-ring accept mask.  The caller owns the occupancy bump
    (``size + accepted``) so its counters stay in this window.
    """
    accepted = active & (size < capacity)
    pos = (head + size) % capacity
    return pos, accepted


def duct_window_jnp(q_avail, q_touch, q_pay, head, size,
                    push_pos, push_acc, push_avail, push_touch, push_pay,
                    recv_now, recv_active,
                    *, max_pops: int) -> WindowResult:
    """jnp twin of the fused window op: push-apply -> drain -> halo-select.

    Same contract as ``ref.duct_window_ref``: the push phase only *applies*
    sends the caller already accepted (drop-iff-full and the slot position
    were decided eagerly at stage time, and ``size`` counts them), then the
    drain pops the longest available FIFO prefix per ring via the lane
    formulation (blocked-offset row-min — gather-free, the same shape of
    work the Pallas kernel does), and the freshest payloads merge into the
    (n, 4, L) halo with ascending-row selects.
    """
    n, d, C = q_avail.shape
    L = q_pay.shape[-1]
    R = n * d
    qa = q_avail.reshape(R, C)
    qt = q_touch.reshape(R, C)
    qp = q_pay.reshape(R, C, L)
    head_f = head.reshape(R)
    size_f = size.reshape(R)
    col = jnp.arange(C, dtype=jnp.int32)[None, :]
    # --- push: masked writes at the staged slots ----------------------
    at = push_acc.reshape(R)[:, None] & (col == push_pos.reshape(R)[:, None])
    qa = jnp.where(at, push_avail.reshape(R)[:, None], qa)
    qt = jnp.where(at, push_touch.reshape(R)[:, None], qt)
    qp = jnp.where(at[:, :, None], push_pay.reshape(R, 1, L), qp)
    # --- drain: longest available FIFO prefix, head-blocking, bounded --
    off = (col - head_f[:, None]) % C
    valid = off < size_f[:, None]
    rnow = jnp.broadcast_to(recv_now[:, None], (n, d)).reshape(R)
    ract = jnp.broadcast_to(recv_active[:, None], (n, d)).reshape(R)
    blocked = valid & (qa > rnow[:, None])
    blocked_off = jnp.min(jnp.where(blocked, off, C), axis=1)
    dr = jnp.minimum(jnp.minimum(blocked_off, size_f), max_pops)
    dr = jnp.where(ract, dr, 0).astype(jnp.int32)
    popped = valid & (off < dr[:, None])
    fresh = popped & (off == dr[:, None] - 1)
    recv_touch = jnp.sum(jnp.where(fresh, qt, 0), axis=1)
    fresh_pay = jnp.sum(jnp.where(fresh[:, :, None], qp,
                                  jnp.zeros((), qp.dtype)), axis=1)
    qa = jnp.where(popped, jnp.inf, qa)
    head2 = (head_f + dr) % C
    size2 = size_f - dr
    halo_pay, halo_win = dense_halo_select(
        (dr > 0).reshape(n, d), fresh_pay.reshape(n, d, L))
    return WindowResult(
        qa.reshape(n, d, C), qt.reshape(n, d, C), qp.reshape(n, d, C, L),
        head2.reshape(n, d), size2.reshape(n, d), dr.reshape(n, d),
        recv_touch.reshape(n, d), halo_pay, halo_win)


def duct_window(q_avail, q_touch, q_pay, head, size,
                push_pos, push_acc, push_avail, push_touch, push_pay,
                recv_now, recv_active,
                *, max_pops: int,
                use_pallas: bool = None,
                interpret: bool = False) -> WindowResult:
    """Backend dispatch for the fused window op: Pallas kernel on TPU (one
    VMEM-resident sweep per block of rings), jnp twin elsewhere.
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU
    parity tests); nothing else reaches the interpreter."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return duct_window_jnp(
            q_avail, q_touch, q_pay, head, size,
            push_pos, push_acc, push_avail, push_touch, push_pay,
            recv_now, recv_active, max_pops=max_pops)
    from repro.kernels.duct_exchange.kernel import duct_window_kernel
    return WindowResult(*duct_window_kernel(
        q_avail, q_touch, q_pay, head, size,
        push_pos, push_acc, push_avail, push_touch, push_pay,
        recv_now, recv_active, max_pops=max_pops,
        interpret=interpret))


class CommitResult(NamedTuple):
    q_avail: jax.Array     # (R, C)
    q_touch: jax.Array     # (R, C)
    q_pay: jax.Array       # (R, C, L)


def duct_commit_jnp(q_avail, q_touch, q_pay, head, size0, pb_cnt,
                    pb_avail, pb_touch, pb_pay) -> CommitResult:
    """jnp twin of the superstep commit: fold the compact pushbuf into the
    base rings.  Push ``j`` of ring ``r`` lands at slot
    ``(head[r] + size0[r] + j) % C`` — the live-tail slot the per-window
    path would have written it to, independent of how the superstep's pops
    interleaved with its pushes (FIFO: base drains all precede pushbuf
    drains, so an already-popped pushbuf entry's slot sits behind the
    advanced head and is dead).  Every ring slot recovers which pushbuf
    index lands on it; the fold is a one-hot multiply-accumulate over the
    W pushbuf columns rather than a ``take_along_axis`` (XLA:CPU lowers
    the (R, C) gather to a serial row loop) or a *sequential* chain of W
    masked writes (each link materializes a full (R, C[, L]) intermediate
    — a superstep-dominating copy storm inside a scan).  The sum-of-
    products form is a pure elementwise DAG, so XLA fuses it into a
    single sweep per output array."""
    R, C = q_avail.shape
    W = pb_avail.shape[1]
    col = jnp.arange(C, dtype=jnp.int32)[None, :]
    j = (col - head[:, None] - size0[:, None]) % C
    wr = j < pb_cnt[:, None]
    hot = [(j == w) for w in range(W)]
    acc_a = sum(jnp.where(hot[w], pb_avail[:, w, None], 0.0)
                for w in range(W))
    acc_t = sum(jnp.where(hot[w], pb_touch[:, w, None], 0)
                for w in range(W))
    acc_p = sum(jnp.where(hot[w][:, :, None], pb_pay[:, w, None, :], 0)
                for w in range(W))
    qa = jnp.where(wr, acc_a, q_avail)
    qt = jnp.where(wr, acc_t, q_touch)
    qp = jnp.where(wr[:, :, None], acc_p, q_pay)
    return CommitResult(qa, qt, qp)


def duct_commit(q_avail, q_touch, q_pay, head, size0, pb_cnt,
                pb_avail, pb_touch, pb_pay,
                *, use_pallas: bool = None,
                interpret: bool = False) -> CommitResult:
    """Backend dispatch for the superstep commit: Pallas kernel on TPU
    (one masked-select sweep per ring block, gather-free), jnp twin
    elsewhere.  Slot-exact with ``ref.duct_commit_ref``; ``interpret`` as
    in :func:`duct_window`."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return duct_commit_jnp(q_avail, q_touch, q_pay, head, size0,
                               pb_cnt, pb_avail, pb_touch, pb_pay)
    from repro.kernels.duct_exchange.kernel import duct_commit_kernel
    return CommitResult(*duct_commit_kernel(
        q_avail, q_touch, q_pay, head, size0, pb_cnt,
        pb_avail, pb_touch, pb_pay, interpret=interpret))


def duct_exchange(q_avail, q_touch, head, size,
                  recv_now, recv_active,
                  send_now, send_active, send_lat, send_touch,
                  *, capacity: int, max_pops: int,
                  use_pallas: bool = None,
                  interpret: bool = False) -> ExchangeResult:
    """Backend dispatch: Pallas kernel on TPU, jnp twin elsewhere.

    ``use_pallas=True`` forces the kernel (with ``interpret`` controlling
    the Pallas interpreter, for CPU parity tests).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return duct_exchange_jnp(
            q_avail, q_touch, head, size, recv_now, recv_active,
            send_now, send_active, send_lat, send_touch,
            capacity=capacity, max_pops=max_pops)
    from repro.kernels.duct_exchange.kernel import duct_exchange_kernel
    return ExchangeResult(*duct_exchange_kernel(
        q_avail, q_touch, head, size, recv_now, recv_active,
        send_now, send_active, send_lat, send_touch,
        capacity=capacity, max_pops=max_pops,
        interpret=interpret))
