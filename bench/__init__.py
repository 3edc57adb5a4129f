"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout on a TPU host. Everything
that belongs to one configuration, traffic mix, generator, reference
or metric sits in a file of its own under this directory and is found
by the name ``BENCHMARK.json`` gives it (see ``spec.py``).
"""
