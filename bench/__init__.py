"""The chip benchmark of the KiSS simulator.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Everything a cell
is made of is found by name: its configuration (``bench/configs``) and
that configuration's plain reference (``bench/reference``), its traffic
mix (``bench/traffic``) and the reader of each of its metrics
(``bench/metrics``).  The yardstick lives here and imports nothing from
the program but its public front door (``repro.sim``) and ``Trace``.
"""
