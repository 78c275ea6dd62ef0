"""Per-layer metric `kv_bytes_per_token`: see `bench.layers.kv_bytes_per_token`."""
from bench import layers


def read(ctx):
    return layers.kv_bytes_per_token(ctx)
