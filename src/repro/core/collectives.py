"""Best-effort collectives for the cross-pod gradient/parameter path.

These functions run inside ``jax.shard_map(..., axis_names={"pod"})`` bodies:
the pod axis is manual (explicit collectives below); data/model axes stay
auto (GSPMD).  They implement the paper's asynchronicity modes on the
gradient path (DESIGN.md §2):

  mode 0  — synchronous cross-pod pmean every step
  mode 1/2— no per-step cross-pod traffic; periodic parameter sync (outer opt)
  mode 3  — staleness-1 delayed cross-pod sum, overlapped with compute;
            optionally lossy-compressed (top-k / int8) with error feedback —
            the "message drop + no retry" analogue
  mode 4  — no cross-pod communication
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from repro.core.modes import AsyncMode

POD_AXIS = "pod"


# ---------------------------------------------------------------------------
# Compressed cross-pod sums
# ---------------------------------------------------------------------------
def cross_pod_sum(tree, axis_name: str = POD_AXIS, compressor=None, residuals=None):
    """Sum a pytree across pods.

    Without a compressor this is a plain psum.  With one, each leaf is encoded
    (lossy, with error feedback), the compact payload is all-gathered across
    pods, and decoded+summed locally — collective bytes shrink by the
    compression ratio.  Returns (summed_tree, new_residuals).
    """
    if compressor is None:
        return lax.psum(tree, axis_name), residuals
    if residuals is None:
        residuals = jax.tree.map(jnp.zeros_like, tree)

    def leaf_sum(leaf, res):
        payload, new_res = compressor.encode(leaf + res)
        gathered = jax.tree.map(
            lambda p: lax.all_gather(p, axis_name, axis=0), payload)
        total = compressor.decode_sum(gathered, leaf.shape, leaf.dtype)
        return total, new_res

    flat, treedef = jax.tree.flatten(tree)
    res_flat = jax.tree.leaves(residuals)
    out = [leaf_sum(l, r) for l, r in zip(flat, res_flat)]
    summed = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_res = jax.tree.unflatten(treedef, [o[1] for o in out])
    return summed, new_res


# ---------------------------------------------------------------------------
# Gradient exchange per asynchronicity mode
# ---------------------------------------------------------------------------
def init_exchange_state(grads_like, mode: AsyncMode, compressor=None):
    state = {}
    if mode == AsyncMode.BEST_EFFORT:
        state["others"] = jax.tree.map(jnp.zeros_like, grads_like)
        if compressor is not None:
            state["residuals"] = jax.tree.map(jnp.zeros_like, grads_like)
    return state


def exchange_gradients(grads, state: dict, mode: AsyncMode,
                       axis_name: str = POD_AXIS, compressor=None):
    """grads: pod-local mean gradients.  Returns (effective_grads, new_state).

    BEST_EFFORT: effective grad at step t combines this pod's fresh gradient
    with the *other* pods' step t-1 gradients (staleness-1).  The cross-pod
    reduction issued here is consumed next step, so the scheduler overlaps it
    with the whole of this step's compute.
    """
    n = lax.axis_size(axis_name)
    if mode == AsyncMode.BARRIER_EVERY_STEP:
        return jax.tree.map(lambda g: g / n, lax.psum(grads, axis_name)), state
    if mode in (AsyncMode.ROLLING_BARRIER, AsyncMode.FIXED_BARRIER,
                AsyncMode.NO_COMM):
        return grads, state  # cross-pod sync handled by the outer optimizer

    assert mode == AsyncMode.BEST_EFFORT
    others_prev = state["others"]
    eff = jax.tree.map(lambda g, o: (g + o) / n, grads, others_prev)
    total, new_res = cross_pod_sum(
        grads, axis_name, compressor, state.get("residuals"))
    others_new = jax.tree.map(lambda t, g: t - g, total, grads)
    new_state = dict(state, others=others_new)
    if compressor is not None:
        new_state["residuals"] = new_res
    return eff, new_state


# ---------------------------------------------------------------------------
# Periodic parameter sync (modes 1/2 outer step)
# ---------------------------------------------------------------------------
def pod_mean(tree, axis_name: str = POD_AXIS):
    n = lax.axis_size(axis_name)
    return jax.tree.map(lambda x: lax.psum(x, axis_name) / n, tree)


def maybe_param_sync(params, do_sync, axis_name: str = POD_AXIS):
    """Average parameters across pods when ``do_sync`` (traced bool) is set.

    The psum always appears in the graph; ``where`` selects its result only on
    sync steps.  (A lax.cond would skip the flops but XLA still provisions the
    collective; measured cost on non-sync steps is the no-op select.)
    """
    mean = pod_mean(params, axis_name)
    return jax.tree.map(lambda m, p: jnp.where(do_sync, m, p), mean, params)
