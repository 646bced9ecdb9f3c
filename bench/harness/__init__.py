"""The on-chip benchmark's own code: the yardstick that later changes to
the program are measured with.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under ``bench/`` and is found
by the name ``BENCHMARK.json`` gives it (``spec.py``). This package holds
what all cells share: the command line (``cli.py``), the chip look and
the peaks table (``device.py``), the traffic generator (``traffic.py``),
where a model's blocks lie in its parameter tree (``layout.py``), the
conventions that compose the references' per-block operation and byte
counts (``flops.py``), the trace reduction
(``xplane.py``), the compile-event capture (``monitor.py``), the
benchmark's own Wanda masks (``wanda.py``) and the comparisons with the
plain references that decide ``correct`` (``walkcheck.py``,
``servecheck.py``).

The program is imported only through its entry points:
``repro.configs.get_config``, ``repro.models.model.build``,
``repro.core.ebft.finetune`` / ``EBFTConfig`` and
``repro.serving.decode.Server`` / ``Request``.
"""
