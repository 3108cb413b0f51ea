"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole run on the CPU at a small size (the harness's
look for a chip skipped), with one fault planted in the program: a call that
returns its weights unchanged, a gradient over half of the batch (the mean
taken over the rest), a completing client altered in the event race, and the
largest leaf of a client's gradient altered where it is produced.  The cell
runs on one chip, so there is no exchange between chips to leave out.  A clean run
at the same size must come out correct, or the faults would prove nothing.
"""
import io
import json
import time

import jax
import pytest

from bench import harness
from bench.tests.small import small_spec

CELLS = ["mamba2_130m.paper_fed"]


def _run(cell):
    out = io.StringIO()
    rc = harness.run(cell, 20261018, 0.5, False, t_process=time.perf_counter(),
                     require_chip=False, spec=small_spec(cell), out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture(autouse=True)
def _fresh():
    from repro.core import stream_device

    jax.clear_caches()
    stream_device._stream_generator.cache_clear()
    yield
    jax.clear_caches()
    stream_device._stream_generator.cache_clear()


def _unchanged(mp):
    import repro.core as core

    real = core.run_generalized_async_sgd

    def fault(w0, source, cfg, eval_fn=None):
        _, rec = real(w0, source, cfg, eval_fn=eval_fn)
        return w0, rec

    mp.setattr(core, "run_generalized_async_sgd", fault)


def _half_batch(mp):
    from repro.fl.engine import DeviceTaskClients

    real = DeviceTaskClients.client_batch

    def fault(self, client_id, server_step):
        b = real(self, client_id, server_step)
        return {k: v[: self.batch_size // 2] for k, v in b.items()}

    mp.setattr(DeviceTaskClients, "client_batch", fault)


def _client_altered(mp):
    from repro.core import stream_device as sd

    real = sd.stream_step

    def fault(state, mu, xs):
        state, ev = real(state, mu, xs)
        return state, ev._replace(j=(ev.j + 1) % mu.shape[0])

    mp.setattr(sd, "stream_step", fault)


def _gradient_altered(mp):
    from repro.fl.engine import DeviceTaskClients

    real = DeviceTaskClients.device_grad

    def fault(self, client_id, params, server_step):
        g = real(self, client_id, params, server_step)
        leaves, tree = jax.tree_util.tree_flatten(g)
        big = max(range(len(leaves)), key=lambda i: leaves[i].size)
        leaves[big] = 2.0 * leaves[big]
        return jax.tree_util.tree_unflatten(tree, leaves)

    mp.setattr(DeviceTaskClients, "device_grad", fault)


@pytest.mark.parametrize("cell", CELLS)
def test_clean_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["checks"]["race_faults"]["value"] == 0


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _client_altered, _gradient_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    r = _run(cell)
    assert not r["correct"], r["checks"]
