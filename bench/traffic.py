"""The benchmark's one traffic generator: everything a cell feeds the program.

A cell's traffic file (``bench/workloads/<cell>.json``) holds parameters
only; this module turns them into arrays.  Three things are made here:

* the federation: client speeds under the paper's two-cluster law
  (a ``frac_fast`` share of the n clients runs ``speed_ratio`` times faster
  than ``mu_slow``), drawn from the run's seed;
* the clients' data shards and the held-out eval batch, from the traffic's
  fixed ``data_seed`` -- ``"lm_markov"``: Zipf unigram plus a per-stream
  first-order Markov successor table, the law of the program's
  ``SyntheticLMStream``;
* the key of each engine call, advancing with the call index.

The shards come from ``data_seed`` and not from the run's seed because the
program's device clients close over their shards, so the data is part of
the compiled program: shards that changed with the seed would make every run
compile anew.  The seed moves the weights, the speed assignment and every
event stream instead.
"""
from __future__ import annotations

import numpy as np

SEED_MOD = 2**31 - 1


def call_seed(seed: int, i: int) -> int:
    """Key seed of engine call ``i`` of a run: distinct per (seed, i), < 2**31."""
    return int((seed * 1_000_003 + 7919 * (i + 1)) % SEED_MOD)


def client_speeds(fed: dict, seed: int) -> np.ndarray:
    """(n,) service rates: ``round(n * frac_fast)`` clients at
    ``mu_slow * speed_ratio``, chosen from the seed, the rest at ``mu_slow``."""
    n = int(fed["n_clients"])
    rng = np.random.default_rng([seed % SEED_MOD, 17])
    n_fast = int(round(n * float(fed["frac_fast"])))
    mu = np.full(n, float(fed["mu_slow"]))
    mu[rng.choice(n, size=n_fast, replace=False)] *= float(fed["speed_ratio"])
    return mu


def make_data(data: dict, n_clients: int, vocab_size: int | None = None):
    """``(shards, eval_batch)`` as device arrays, per ``data["kind"]``.

    ``shards`` maps each batch key to an ``(n_clients, shard_rows, ...)``
    array; ``eval_batch`` holds ``eval_rows`` held-out rows of the same keys.
    """
    kind = data["kind"]
    if kind == "lm_markov":
        return _lm_markov(data, n_clients, int(vocab_size))
    raise ValueError(f"unknown data kind {kind!r}")


def _lm_markov(data: dict, n: int, V: int):
    """Token shards: each client (and the eval set) is its own stream with
    its own successor table of ``branch`` likely next tokens per token; a
    token follows the table with probability ``follow``, else it is a fresh
    Zipf draw.  The first token of a row is a Zipf draw."""
    import jax
    import jax.numpy as jnp

    rows, S = int(data["shard_rows"]), int(data["seq_len"])
    ev_rows, branch = int(data["eval_rows"]), int(data["branch"])
    follow = float(data["follow"])

    @jax.jit
    def gen(key):
        ks = jax.random.split(key, 5)
        streams = n + 1                     # clients, then the eval stream
        succ = jax.random.randint(ks[0], (streams, V, branch), 0, V, jnp.int32)
        zipf = 1.0 / jnp.arange(1, V + 1, dtype=jnp.float32)
        cdf = jnp.cumsum(zipf) / jnp.sum(zipf)

        def zipf_draw(k, shape):
            u = jax.random.uniform(k, shape)
            return jnp.minimum(jnp.searchsorted(cdf, u, side="right"), V - 1).astype(jnp.int32)

        total = n * rows + ev_rows
        stream_of = jnp.concatenate([
            jnp.repeat(jnp.arange(n, dtype=jnp.int32), rows),
            jnp.full((ev_rows,), n, jnp.int32),
        ])
        first = zipf_draw(ks[1], (total,))
        fol = jax.random.uniform(ks[2], (S, total)) < follow
        pick = jax.random.randint(ks[3], (S, total), 0, branch, jnp.int32)
        fresh = zipf_draw(ks[4], (S, total))

        def step(tok, x):
            f, c, r = x
            nxt = jnp.where(f, succ[stream_of, tok, c], r)
            return nxt, nxt

        _, rest = jax.lax.scan(step, first, (fol, pick, fresh))
        toks = jnp.concatenate([first[None], rest], axis=0).T   # (total, S+1)
        return toks[:, :-1], toks[:, 1:]

    toks, labs = gen(jax.random.PRNGKey(int(data["data_seed"])))
    cut = n * rows
    shards = {"tokens": toks[:cut].reshape(n, rows, S),
              "labels": labs[:cut].reshape(n, rows, S)}
    return shards, {"tokens": toks[cut:], "labels": labs[cut:]}

