"""Per-layer metric `queue_wait_p95_ms`: see `bench.layers.queue_wait_p95_ms`."""
from bench import layers


def read(ctx):
    return layers.queue_wait_p95_ms(ctx)
