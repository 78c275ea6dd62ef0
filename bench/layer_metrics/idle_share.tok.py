"""Per-layer metric `idle_share.tok`: see `bench.layers.idle_share`."""
from bench import layers


def read(ctx):
    return layers.idle_share(ctx)
