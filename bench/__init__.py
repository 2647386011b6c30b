"""On-chip benchmark of the SA-leverage Nystrom KRR system (see BENCHMARK.json).

Run one cell once:  python3 -m bench.run --workload <name> --seed <n>
                    --seconds <s> --trace <0|1>
"""
