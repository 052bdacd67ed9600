"""On-chip benchmark of the serving stack: one cell per run, driven by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
"""
