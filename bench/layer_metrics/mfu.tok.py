"""Per-layer metric `mfu.tok`: see `bench.layers.mfu`."""
from bench import layers


def read(ctx):
    return layers.mfu(ctx)
