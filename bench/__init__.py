"""The chip benchmark of the async-FL engine (``python bench/run.py``).

Layout, each piece found by name from BENCHMARK.json:

* ``configs/<config>.json`` -- a model configuration's sizes, source, cuts and
  assumptions; ``configs/<config>.py`` beside it -- the program's model step
  for those sizes, the benchmark's weights from the seed, the plain float32
  reference and the model's FLOP count;
* ``workloads/<cell>.json`` -- a cell's traffic: the federation (n, C, speed
  law, sampling, step size), the data law and its sizes, the engine call
  (updates per call, calls checked, calls traced) and the limits of the
  compared numbers, read by the one generator ``traffic.py``;
* ``metrics/<metric>.py`` -- a per-layer metric's reader;
* ``kernels/<kernel>.py`` -- a kernel's operations and bytes from its shapes;
* ``peaks.json`` -- the chips' published peaks by ``device_kind``.

To add a cell: add ``workloads/<cell>.json`` and the ``workloads`` entry in
BENCHMARK.json (and for a new model its two ``configs`` files and the
``configs`` entry).  To add a per-layer metric: add ``metrics/<metric>.py``
and its ``per_layer`` entry.  The harness (``harness.py``), the reference
checks (``check.py``, ``race.py``), the trace reduction (``trace.py``) and the
generator (``traffic.py``) stay as they are.
"""
