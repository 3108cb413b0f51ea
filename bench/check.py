"""How ``correct`` is decided: the plain reference replayed along the checked
events of the first calls, and the numbers compared with it.

Set-up drives the program through its first ``check_calls`` engine calls,
the window's own calls on the window's own runner.  After the window the
reference follows those calls: from the same starting weights, along the
same events (checked one by one by `bench.race`), on the same batches, it
applies Algorithm 1 in float32 at matmul precision "highest" -- a float32
master copy, a ring of C dispatch-time snapshots, ``w -= eta / (n p_J) *
grad`` at the completing task's snapshot -- and hands the next call its
weights in the dtypes the configuration stores them in, as the program's
engine does.  It evaluates the cell's eval loss at the end of each call.

Numbers a cell may compare, each with its limit from the cell's traffic
file (the traffic file names those it compares; `numbers` has the rest):

* ``loss_gap``: the largest relative gap between the program's and the
  reference's eval loss at the end of a checked call;
* ``change1_gap`` / ``change_gap``: by the worst kept leaf, the gap between
  the norms of the program's and the reference's weight change over the first
  call / over all checked calls, against the reference's norm of that leaf or
  of the median leaf, whichever is larger;
* ``race_faults``: events, step times and per-client totals of the checked
  calls that the closed network's law does not allow (limit 0);
* ``window_compiles``: compilations inside the measured window (limit 0).

Which leaves the change gaps keep is decided on the reference's change over
the first call, by two rules:

* a leaf whose change is under a thousandth of the median leaf's moves by
  round-off alone (``LEAF_FLOOR``);
* a leaf whose change is under ``ULPS`` units in the last place of its stored
  dtype, at its starting root-mean-square magnitude, moves by a countable
  number of roundings: the weights are handed between calls in their stored
  dtype, so a change of u units is about u**2 elements that crossed a
  rounding boundary, and the chance in that count moves its norm by about
  1 / (2 u).  At 100 units that is 0.5 %.  A bfloat16 leaf that
  starts at ones (a norm's scale, the skip weight D) moves by one or two
  units a call and is left out; a float32 leaf is kept.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: the program's device clients read client j's batch at step k as the rows
#: ``[starts[k % OFFSET_BLOCK], + batch)`` of its shard, with ``starts`` drawn
#: by ``jax.random.randint(PRNGKey(data_seed), (OFFSET_BLOCK,), 0, rows -
#: batch + 1)`` -- the feed the reference has to read the same batches from
OFFSET_BLOCK = 8192
LEAF_FLOOR = 1e-3
ULPS = 100.0


def batch_starts(data_seed: int, shard_rows: int, batch: int) -> np.ndarray:
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(data_seed), (OFFSET_BLOCK,), 0, shard_rows - batch + 1))


class Reference:
    """The reference of one cell: Algorithm 1 over a ring of snapshots, one
    compiled scan over a call's events per precision."""

    def __init__(self, model, shards, eval_batch, batch: int, starts, C: int, half_batch=False):
        self.model, self.shards, self.ev, self.C = model, shards, eval_batch, C
        self.starts = np.asarray(starts)
        self.rows = batch // 2 if half_batch else batch
        self._call, self._eval = {}, {}

    def _fns(self, precision: str):
        if precision not in self._call:
            model, rows, C = self.model, self.rows, self.C
            master = model.master_dtype(precision)

            def window(shards, j, st):
                def cut(a):
                    idx = (j, st) + (0,) * (a.ndim - 2)
                    return jax.lax.dynamic_slice(a, idx, (1, rows) + a.shape[2:])[0]
                return {k: cut(v) for k, v in shards.items()}

            def call(w_in, shards, J, slot, start, scale):
                w = jax.tree_util.tree_map(lambda a: a.astype(master), w_in)
                ring = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (C,) + a.shape), w)

                def event(carry, ev):
                    w, ring = carry
                    j, s, st, sc = ev
                    snap = jax.tree_util.tree_map(lambda r: r[s], ring)
                    g = jax.grad(lambda ww: model.ref_loss(ww, window(shards, j, st), precision))(snap)
                    w = jax.tree_util.tree_map(
                        lambda a, b: (a - sc * b.astype(a.dtype)).astype(a.dtype), w, g)
                    ring = jax.tree_util.tree_map(lambda r, a: r.at[s].set(a), ring, w)
                    return (w, ring), None

                (w, _), _ = jax.lax.scan(event, (w, ring), (J, slot, start, scale))
                return jax.tree_util.tree_map(lambda a, b: model.store(a, precision).astype(b.dtype),
                                              w, w_in)

            self._call[precision] = jax.jit(call)
            self._eval[precision] = jax.jit(lambda w, ev: model.ref_loss(w, ev, precision))
        return self._call[precision], self._eval[precision]

    def replay(self, w0, calls, precision: str = "float32"):
        """Follow ``calls`` (a list of `race.Events`) from ``w0``.  Returns the
        weights handed on after each call and the eval loss there."""
        call, ev = self._fns(precision)
        outs, losses = [], []
        w = w0
        with jax.default_matmul_precision("highest"):
            for e in calls:
                w = call(w, self.shards, jnp.asarray(e.J, jnp.int32), jnp.asarray(e.slot, jnp.int32),
                         jnp.asarray(self.starts[e.k % OFFSET_BLOCK], jnp.int32),
                         jnp.asarray(e.scale, jnp.float32))
                outs.append(w)
                losses.append(float(ev(w, self.ev)))
        return outs, losses


@jax.jit
def _leaf_norms(w, w0):
    return jnp.stack([
        jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).ravel())
        for a, b in zip(jax.tree_util.tree_leaves(w), jax.tree_util.tree_leaves(w0))
    ])


def leaf_names(tree) -> list:
    """Path names of a tree's leaves, in the tree's leaf order."""
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def change_norms(w, w0) -> np.ndarray:
    """Per-leaf norm of ``w - w0``, in the tree's leaf order."""
    return np.asarray(_leaf_norms(w, w0), np.float64)


@jax.jit
def _leaf_rms(w):
    return jnp.stack([jnp.sqrt(jnp.mean(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree_util.tree_leaves(w)])


def leaf_units(w) -> np.ndarray:
    """Per leaf, the unit in the last place of its dtype at its
    root-mean-square magnitude (0 for a leaf of zeros), in leaf order."""
    rms = np.asarray(_leaf_rms(w), np.float64)
    eps = np.array([float(jnp.finfo(a.dtype).eps) for a in jax.tree_util.tree_leaves(w)])
    with np.errstate(divide="ignore"):
        return np.where(rms > 0, np.exp2(np.floor(np.log2(rms))) * eps, 0.0)


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """Per kept leaf, ``|prog - ref|`` against the reference norm of the leaf
    or of the median leaf, whichever is larger."""
    den = np.maximum(ref, np.median(ref))
    return (np.abs(prog - ref) / den)[keep]


def numbers(prog_norms1, prog_normsN, prog_losses, ref_norms1, ref_normsN, ref_losses, units):
    """Every number a cell may compare; its traffic file's ``limits`` say
    which it does.  ``change1_gap`` is the first call's weight change,
    ``change_gap`` the change over all checked calls, each by the worst kept
    leaf; ``units`` is `leaf_units` of the starting weights."""
    keep = (ref_norms1 >= LEAF_FLOOR * np.median(ref_norms1)) & (ref_norms1 >= ULPS * units)
    lp, lr = np.asarray(prog_losses, np.float64), np.asarray(ref_losses, np.float64)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "change1_gap": float(np.max(leaf_gaps(prog_norms1, ref_norms1, keep))),
        "change_gap": float(np.max(leaf_gaps(prog_normsN, ref_normsN, keep))),
    }


def verdict(values: dict, limits: dict) -> bool:
    """Correct when every compared number is finite and within its limit."""
    return all(np.isfinite(values[k]) and values[k] <= limits[k] for k in limits)
