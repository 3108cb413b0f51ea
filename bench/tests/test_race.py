"""The reference event race against the program's stream, on the CPU."""
import dataclasses

import numpy as np
import pytest

from bench import race
from bench import traffic as gen

FED = dict(n_clients=20, concurrency=5, frac_fast=0.5, speed_ratio=10.0, mu_slow=1.0)


def _stream(seed=7, T=400):
    from repro.core.stream_device import generate_stream

    mu = gen.client_speeds(FED, seed)
    p = np.where(mu > 1, 0.03, 0.07)
    p = p / p.sum()
    cs = gen.call_seed(seed, 0)
    s = generate_stream(mu, p, 5, T, seed=cs)
    n = mu.size
    comp = np.bincount(s.J, minlength=n)
    delays = np.bincount(s.J, weights=s.delay_steps, minlength=n) / np.maximum(comp, 1)
    return s, mu, p, cs, comp, delays


def _check(s, mu, p, cs, comp, delays, times=None):
    return race.check_call(s, mu, p, 5, 0.05, cs, s.t if times is None else times, comp, delays)


def test_program_stream_obeys_the_law():
    s, mu, p, cs, comp, delays = _stream()
    ev = _check(s, mu, p, cs, comp, delays)
    assert ev.faults == 0
    np.testing.assert_array_equal(ev.J, s.J)
    np.testing.assert_array_equal(ev.slot, s.slot)
    np.testing.assert_allclose(ev.scale, 0.05 / (mu.size * p[s.J]))


def test_own_events_are_the_programs():
    s, mu, p, cs, _, _ = _stream()
    own = race.check_call(None, mu, p, 5, 0.05, cs, T=s.J.size)
    assert np.mean(own.J == s.J) > 0.99


@pytest.mark.parametrize("field", ["J", "K", "slot", "delay_steps"])
def test_an_altered_event_is_a_fault(field):
    s, mu, p, cs, comp, delays = _stream()
    col = getattr(s, field).copy()
    k = 123
    col[k] = (col[k] + 1) % (s.C if field == "slot" else mu.size)
    ev = _check(dataclasses.replace(s, **{field: col}), mu, p, cs, comp, delays)
    assert ev.faults > 0


def test_altered_times_and_totals_are_faults():
    s, mu, p, cs, comp, delays = _stream()
    t = s.t.copy()
    t[50] *= 1.01
    assert _check(s, mu, p, cs, comp, delays, times=t).faults > 0
    c = comp.copy()
    c[0] += 1
    assert _check(s, mu, p, cs, c, delays).faults > 0
    d = delays.copy()
    d[3] += 0.5
    assert _check(s, mu, p, cs, comp, d).faults > 0
