"""The on-chip benchmark: ``python3 bench/run.py --workload <cell> ...``.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

  configs/<file>.json     a configuration's sizes, as run; it names its
                          system adapter and its plain reference
  systems/<name>.py       how one kind of system is built, warmed,
                          driven and checked (``cnn``, ``lm``)
  traffic/<name>.json     a traffic mix: parameters that ``loadgen``
                          (one general generator) reads
  metrics/<name>.py       one per-layer metric's reader
  counts/, peaks.py       operation and byte counts, the chip's peaks
"""
