"""The readers of the program's own layers on a synthetic trace reduction,
scope table and span buffer: each sums what its scope holds inside the
window, leaves out ops in no scope or outside the window, and reads nothing
from a program that records no table or spans."""
import collections
import importlib
import os
import sys

import pytest

from bench.harness import Context
from bench.trace import Reduction

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MS = 1_000_000  # ns
# one traced window of 2 calls x 4 updates, 100 ms long
OPS = [
    (0, 30 * MS, "%fusion.1 = bf16[4,64] fusion(%a), kind=kLoop, calls=%fc.1"),
    (30 * MS, 50 * MS, "%ssd_scan.2 = (bf16[2]) custom-call(%b)"),
    (50 * MS, 56 * MS, "%dynamic-update-slice.3 = f32[10,8] dynamic-update-slice(%c)"),
    (56 * MS, 56 * MS + 400_000, "%fusion.4 = s32[] fusion(%d), kind=kLoop"),
    (57 * MS, 61 * MS, "%fusion.5 = f32[] fusion(%e), kind=kLoop"),
    (61 * MS, 62 * MS, "%copy.6 = f32[8] copy(%f)"),              # in no scope
    (95 * MS, 110 * MS, "%fusion.1 = bf16[4,64] fusion(%a), kind=kLoop"),  # half outside
]
TABLE = {"fusion.1": "fl.grad", "ssd_scan.2": "fl.grad", "dynamic-update-slice.3": "fl.ring",
         "fusion.4": "fl.race", "fusion.5": "fl.eval"}
SPANS = [  # (name, start_ns, end_ns, depth): an untraced call, then the 2 traced calls
    ("fl.prepare", 0, 1 * MS, 1), ("fl.wait", 2 * MS, 50 * MS, 1), ("fl.call", 0, 51 * MS, 0),
    ("fl.prepare", 60 * MS, 62 * MS, 1), ("fl.launch", 62 * MS, 63 * MS, 1),
    ("fl.wait", 63 * MS, 90 * MS, 1), ("fl.readback", 90 * MS, 93 * MS, 1),
    ("fl.call", 60 * MS, 93 * MS, 0),
    ("fl.prepare", 100 * MS, 101 * MS, 1), ("fl.wait", 101 * MS, 140 * MS, 1),
    ("fl.readback", 140 * MS, 146 * MS, 1), ("fl.call", 100 * MS, 146 * MS, 0),
]


def _ctx():
    red = Reduction(window=(0, 100 * MS), busy_ns=62 * MS, devices=1, ops=OPS)
    return Context(red, 0.0, None, {}, traced_calls=2, updates_per_call=4,
                   device_kind="TPU v5 lite", chips=1)


@pytest.fixture
def program(monkeypatch):
    obs = importlib.import_module("repro.obs")
    monkeypatch.setattr(obs, "scope_table", dict(TABLE))
    monkeypatch.setattr(obs, "spans", collections.deque(SPANS))
    return obs


@pytest.mark.parametrize("name, want", [
    ("grad_ms_per_update", (30 + 20 + 5) / 8),          # the op cut at the window's end
    ("ring_ms_per_update", 6 / 8),
    ("race_us_per_update", 400 / 8),
    ("eval_ms_per_call", 4 / 2),
    ("host_ms_per_call", ((33 - 27) + (46 - 39)) / 2),  # the last 2 calls, less their waits
])
def test_reader(program, name, want):
    assert _reader(name).read(_ctx()) == pytest.approx(want, rel=1e-12)


READERS = ["grad_ms_per_update", "ring_ms_per_update", "race_us_per_update",
           "eval_ms_per_call", "host_ms_per_call"]


@pytest.mark.parametrize("name", READERS)
def test_program_without_obs_gives_nothing(monkeypatch, name):
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails, as in an older program
    assert _reader(name).read(_ctx()) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_recorded_gives_nothing(monkeypatch, name):
    obs = importlib.import_module("repro.obs")
    monkeypatch.setattr(obs, "scope_table", {})
    monkeypatch.setattr(obs, "spans", collections.deque())
    assert _reader(name).read(_ctx()) is None


def test_scope_with_no_op_in_the_window_gives_nothing(program):
    program.scope_table["fusion.5"] = "fl.ctrl"
    assert _reader("eval_ms_per_call").read(_ctx()) is None
