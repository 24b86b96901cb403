"""The benchmark: ``python3 benchmarks/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. See PERF.md."""
