"""Per-layer metric `chunk_roofline.tok`: see `bench.layers.chunk_roofline`."""
from bench import layers


def read(ctx):
    return layers.chunk_roofline(ctx)
