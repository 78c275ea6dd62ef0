"""Per-layer metric `decode_roofline.tok`: see `bench.layers.decode_roofline`."""
from bench import layers


def read(ctx):
    return layers.decode_roofline(ctx)
