"""How ``correct`` is decided: the plain reference replayed along the checked
events of the first calls, and the numbers compared with it.

Set-up drives the program through its first ``check_calls`` engine calls,
the window's own calls on the window's own runner.  After the window the
reference follows those calls: from the same starting weights, along the
same events (checked one by one by `bench.race`), on the same batches, it
applies Algorithm 1 in float32 at matmul precision "highest" -- a float32
master copy, a ring of C dispatch-time snapshots kept in host memory,
``w -= eta / (n p_J) * grad`` at the completing task's snapshot -- and
hands the next call its weights in the dtypes the configuration stores them
in, as the program's engine does.  It evaluates the cell's eval loss at the end of each call.

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
    """The reference of one cell: Algorithm 1 event by event, one compiled
    step per precision, the ring of C snapshots in host memory.

    On the device the replay holds the master copy, one snapshot and its
    gradient (plus the step's activations), whatever C is; the host holds
    the distinct snapshots that later events of the call still read, at most
    ``min(C, T + 1)`` copies of the weights in the master dtype, and the
    weights handed on after each call."""

    def __init__(self, model, shards, eval_batch, batch: int, starts, C: int, half_batch=False):
        self.model, self.shards, self.ev, self.C = model, shards, eval_batch, C
        self.starts = np.asarray(starts)
        self.rows = batch // 2 if half_batch else batch
        self._fn = {}

    def _fns(self, precision: str, w_in):
        """``(step, cast, store, eval)`` of one precision; the step is compiled
        once, for the tree of ``w_in`` in the master dtype."""
        if precision not in self._fn:
            model, rows = self.model, self.rows
            master = model.master_dtype(precision)
            tmap = jax.tree_util.tree_map

            def step(w, snap, shards, j, st, sc):
                def cut(a):
                    idx = (j, st) + (0,) * (a.ndim - 2)
                    return jax.lax.dynamic_slice(a, idx, (1, rows) + a.shape[2:])[0]
                batch = {k: cut(v) for k, v in shards.items()}
                g = jax.grad(lambda ww: model.ref_loss(ww, batch, precision))(snap)
                return tmap(lambda a, b: (a - sc * b.astype(a.dtype)).astype(a.dtype), w, g)

            cast = jax.jit(lambda t: tmap(lambda a: a.astype(master), t))
            w = jax.eval_shape(cast, w_in)
            scalar = lambda d: jax.ShapeDtypeStruct((), d)
            compiled = jax.jit(step, donate_argnums=0).lower(
                w, w, self.shards, scalar(jnp.int32), scalar(jnp.int32), scalar(jnp.float32)).compile()
            dtypes = tmap(lambda a: a.dtype, w_in)
            store = jax.jit(lambda w: tmap(
                lambda a, d: model.store(a, precision).astype(d), w, dtypes))
            ev = jax.jit(lambda w, ev: model.ref_loss(w, ev, precision))
            self._fn[precision] = (compiled, cast, store, ev)
        return self._fn[precision]

    def step_bytes(self, precision: str = "float32") -> dict:
        """Device bytes of the compiled event step (compiled by `replay`):
        arguments, outputs, the outputs that alias a donated argument,
        temporaries, and what the step holds in all."""
        m = self._fn[precision][0].memory_analysis()
        parts = {"argument": m.argument_size_in_bytes, "output": m.output_size_in_bytes,
                 "alias": m.alias_size_in_bytes, "temp": m.temp_size_in_bytes}
        parts["total"] = parts["argument"] + parts["output"] - parts["alias"] + parts["temp"]
        return {k: int(v) for k, v in parts.items()}

    def replay(self, w0, calls, precision: str = "float32"):
        """Follow ``calls`` (a list of `race.Events`) from ``w0``.  Returns the
        weights handed on after each call, in host memory, and the eval loss
        there."""
        outs, losses = [], []
        with jax.default_matmul_precision("highest"):
            step, cast, store, ev = self._fns(precision, w0)
            for e in calls:
                w = store(self._call(step, cast(outs[-1] if outs else w0), e))
                losses.append(float(ev(w, self.ev)))
                outs.append(jax.device_get(w))
                del w
        return outs, losses

    def _call(self, step, w, e):
        """One call's events from the master copy ``w`` (donated).  Version v
        of the weights is ``w`` after v events; every slot starts at version
        0, and event k reads the version its slot last took and writes
        version k + 1 into it.  A version goes to the host only where an
        event reads it, and leaves it after its last read."""
        slot = np.asarray(e.slot)
        ver, src = [0] * self.C, []
        for k, s in enumerate(slot):
            src.append(ver[s])
            ver[s] = k + 1
        last = {v: k for k, v in enumerate(src)}
        host = {0: jax.device_get(w)}
        J, sc = np.asarray(e.J, np.int32), np.asarray(e.scale, np.float32)
        st = self.starts[np.asarray(e.k) % OFFSET_BLOCK].astype(np.int32)
        for k, v in enumerate(src):
            snap = jax.device_put(host[v])
            # waited for, so that no later snapshot is uploaded while this
            # one is held: one snapshot on the device at a time
            w = jax.block_until_ready(step(w, snap, self.shards, J[k], st[k], sc[k]))
            del snap
            if k + 1 in last:
                host[k + 1] = jax.device_get(w)
            for u in [u for u in host if last[u] == k]:
                del host[u]
        return w


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
