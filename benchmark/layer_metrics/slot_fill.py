"""Slots in use of the slots declared: pool_metrics.slot_fill, over the
metric history of the benchmark this file is part of."""

import os

import pool_metrics

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx, params):
    return pool_metrics.slot_fill(ctx, params, BENCH_DIR)
