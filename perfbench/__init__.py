"""On-chip benchmark of the Parrot simulator: federated fine-tuning rounds
of full-width client models on a TPU, driven by ``BENCHMARK.json``.

Run ``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout that holds a TPU.
"""
