"""The benchmark: cells of a model configuration under a job, run on the chip
(``python3 bench/run.py --workload <cell> ...``)."""
