"""The slot pool's bytes on the device: pool_metrics.slot_pool_gb, over the
metric history of the benchmark this file is part of."""

import os

import pool_metrics

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx, params):
    return pool_metrics.slot_pool_gb(ctx, params, BENCH_DIR)
