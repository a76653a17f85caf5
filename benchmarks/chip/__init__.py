"""The chip benchmark: one cell of ``BENCHMARK.json`` per run, on a TPU.

``run.py`` is the entry point. Everything that belongs to one model or
deployment, one traffic mix or one metric is a file of its own that the
harness finds by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the configuration as it is run;
- ``traffic/<mix>.json``: the parameters of a traffic mix;
- ``metrics/<metric>.py``: a reader with ``read(obs) -> float | None``.
  A metric split by cell group (``step_mfu.chat``, ``step_mfu.code``)
  may share one reader named by its base, ``metrics/step_mfu.py``;
- ``counts/<kernel-or-step>.py``: operations and bytes of a kernel or step;
- ``peaks.json``: the chip's published peaks, keyed by ``device_kind``.

``drivers/`` holds one general driver per kind of system (today the
serving fabric); a configuration names its driver.
"""
