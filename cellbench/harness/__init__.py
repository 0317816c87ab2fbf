"""The cell benchmark's harness: everything that decides a number.

``run.py`` drives one cell through it.  The modules here hold the
yardstick: traffic generation (``traffic``), the corpus and queries
(``corpus``), the plain reference that decides ``correct``
(``reference``), the trace reduction (``trace``), the work counts of the
kernels and the search step (``work``), and the table of device peaks
(``peaks``).  What belongs to one configuration, traffic mix or per-layer
metric lives in its own file under ``configs/``, ``traffic/`` and
``metrics/``; ``spec`` finds those files by the names in
``BENCHMARK.json``.
"""
