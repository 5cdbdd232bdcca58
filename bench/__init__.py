"""On-chip benchmark of the federated round (see ``BENCHMARK.json``).

Run one cell once from the checkout root:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
