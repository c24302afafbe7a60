"""On-chip serving benchmark: one cell (configuration x traffic mix) per run.

Entry point: ``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.  Everything that belongs to one configuration, traffic mix,
cell or metric is a file of its own under ``bench/``, found by name.
"""
