"""Readings that set the limits of a cell's compared numbers.

    python bench/control.py --workload <cell> --what program control half_batch \
        --seeds 1 2 3 [--out FILE]

Not part of a benchmark run.  For each seed it prints one JSON line of the
numbers `bench.check` compares:

* ``program``: the program's first calls against the reference, exactly as a
  run checks them (the lower readings; one process, one compile, every seed);
* ``control``: the reference put in the program's place and computed in the
  precision one step below the configuration's (``control_precision`` in the
  sizes file: float8 e4m3 for a bfloat16 model, bfloat16 for a float32 one),
  against the float32 reference, along the reference's own events;
* ``half_batch``: the reference with half of each batch left out (the mean
  taken over the rest), against the float32 reference.

A step that leaves the weights unchanged reads 1 on both change gaps by
construction and needs no run.  Needs the chip for the cell's own sizes;
``bench/tests/test_control.py`` runs it at a small size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(spec, what: list, seeds: list, out=sys.stdout) -> list:
    """One dict of numbers per (what, seed)."""
    from bench import check as chk
    from bench import harness, race
    from bench import traffic as gen

    rows = []
    cell = harness.Cell(spec, seeds[0])
    d = spec.traffic["data"]
    check_calls = int(spec.traffic["engine"]["check_calls"])
    starts = chk.batch_starts(int(d["data_seed"]), int(d["shard_rows"]), int(d["batch"]))
    low = spec.sizes["control_precision"]
    if "program" in what:
        for seed in seeds:
            t0 = time.perf_counter()
            cell.reseed(seed)
            _, checked = harness.first_calls(cell, check_calls)
            values, notes = harness.compare(cell, checked, release=False)
            rows.append(_emit(out, "program", seed, values, notes, t0))
    cell.release()
    ref = chk.Reference(cell.model, cell.shards, cell.eval_batch, int(d["batch"]), starts, cell.C)
    half = chk.Reference(cell.model, cell.shards, cell.eval_batch, int(d["batch"]), starts, cell.C,
                         half_batch=True)
    for seed in seeds if ("control" in what or "half_batch" in what) else []:
        t0 = time.perf_counter()
        cell.reseed(seed)
        events = [race.check_call(None, cell.mu, cell.p, cell.C, cell.eta, gen.call_seed(seed, i),
                                  T=cell.T) for i in range(check_calls)]
        base_w, base_l = ref.replay(cell.w0, events)
        b1, bN = chk.change_norms(base_w[0], cell.w0), chk.change_norms(base_w[-1], cell.w0)
        units = chk.leaf_units(cell.w0)
        del base_w
        for kind in ("control", "half_batch"):
            if kind not in what:
                continue
            r, prec = (ref, low) if kind == "control" else (half, "float32")
            ws, ls = r.replay(cell.w0, events, prec)
            n1, nN = chk.change_norms(ws[0], cell.w0), chk.change_norms(ws[-1], cell.w0)
            del ws
            values = chk.numbers(n1, nN, ls, b1, bN, base_l, units)
            notes = {"losses": ls, "reference_losses": base_l,
                     "leaf_norms": [[name, *map(float, v)] for name, *v in zip(
                         chk.leaf_names(cell.w0), n1, b1, nN, bN, units)]}
            rows.append(_emit(out, kind, seed, values, notes, t0))
    return rows


def _emit(out, kind, seed, values, notes, t0):
    row = {"what": kind, "seed": seed, **values, "notes": notes, "seconds": time.perf_counter() - t0}
    print(json.dumps(row, default=float), file=out, flush=True)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", nargs="+", default=["program", "control", "half_batch"])
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    import jax

    from bench import harness

    if jax.devices()[0].platform == "cpu":
        print("bench.control: no accelerator; the readings are of the chip", file=sys.stderr)
        return 3
    harness.use_cache()
    out = open(a.out, "a") if a.out else sys.stdout
    readings(harness.load_spec(a.workload), a.what, a.seeds, out=out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
