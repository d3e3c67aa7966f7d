"""Bucket launches plus serial SVD dispatches per whole-set compression pass (CompressionReport.exec_stats)."""

from bench.harness import readers


def read(data):
    return readers.launches_per_pass(data)
