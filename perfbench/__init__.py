"""The benchmark: `python perfbench/run.py --workload <cell> ...`.

Everything that decides a number lives here, where a PR that claims a
gain cannot change it: traffic generation, the reduction from traces
and counters to metrics, the table of peaks, FLOPs arithmetic and the
comparison that decides `correct`. See perfbench/README.md.
"""
