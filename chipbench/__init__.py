"""The chip benchmark of split serving: VGG16 cut at a published split point,
its tail served by ``TailServer`` on a TPU under an open loop of edge frames.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own that the harness finds by the name in
``BENCHMARK.json``:

* ``configs/<config>.json``: the model's sizes, cut and wire, with its
  plain reference in ``references/<reference>.py``;
* ``workloads/<cell>.json``: the arrival process and its rate;
* ``metrics/<metric>.py`` (or ``metrics/<family>.py`` for a metric named
  ``<family>.<suffix>``): a reader that takes the number from the run's
  record.

Run one cell from the checkout's root::

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
