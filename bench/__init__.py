"""On-chip benchmark of the AMTL learner and learn-while-serve server.

Run as `python3 -m bench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` from the repository root; see bench/run.py.
"""
