"""Benchmark driver — one section per paper artifact.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig1,fig9

Sections:
  fig1      collectives: bulk vs one-sided across message sizes (§3)
  fig6_8    embedding-bag phase times across tables/batch/pooling (§4.4)
  fig9      local-vs-distributed projection (§5.2)
  beyond    bf16 reduce-scatter + hot-row replication (beyond the paper)

Every section prints modeled figures; the chip benchmark is bench/run.py.
"""
from __future__ import annotations

import argparse


def _section(name):
    print(f"\n{'='*72}\n== {name}\n{'='*72}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,fig6_8,fig9,beyond")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    if want("fig1"):
        _section("Fig 1: collective latency, bulk vs one-sided")
        from benchmarks import collectives
        collectives.main()
    if want("fig6_8"):
        _section("Figs 6-8: embedding-bag phase times (tables/batch/pooling)")
        from benchmarks import embedding_bag_phases
        embedding_bag_phases.main()
    if want("fig9"):
        _section("Fig 9: local vs distributed projection")
        from benchmarks import distributed_projection
        distributed_projection.main()
    if want("beyond"):
        _section("Beyond-paper: bf16 reduce-scatter + hot-row replication")
        from benchmarks import beyond_paper
        beyond_paper.main()


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
