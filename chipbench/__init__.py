"""The chip benchmark: one data-driven harness for every cell in BENCHMARK.json.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, model family, traffic mix or
metric sits in a file of its own under this directory and is found by name:

  configs/<config>.json        model widths, family, ServeConfig fields and
                               replicas, slot arithmetic
  traffic/<mix>.json           parameters of one traffic mix
  traffic/kinds/<kind>.py      the generator a mix names (closed_loop, sessions)
  metrics/<metric>.py          one reader per metric: ``read(run) -> float | None``
  roofline/peaks.json          chip peaks keyed by ``device_kind``
  roofline/<kernel>.py         operations and bytes of one kernel's call
  models/<family>.py           weights, program configuration, reference
                               forward and FLOPs of one architecture

``run.py`` never imports JAX: it starts ``server.py``, which holds the chip,
and drives it over HTTP as a client would.
"""
