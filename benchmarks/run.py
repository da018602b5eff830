"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import sys

from . import paper_figures as F


def main() -> None:
    suites = [
        F.fig5_matmul_memory,
        F.fig6_iris_training,
        F.fig78_training_memory,
        F.fig9_mnist_training,
        F.fig10_inference,
        F.fig1113_mnist_memory,
        F.table1_sizes,
        F.cte_growth,
    ]
    print("name,us_per_call,derived")
    for suite in suites:
        for r in suite():
            print(f"{r['name']},{r['us_per_call']:.1f},\"{r['derived']}\"")
            sys.stdout.flush()


if __name__ == "__main__":
    main()
