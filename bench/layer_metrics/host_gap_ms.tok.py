"""Per-layer metric `host_gap_ms.tok`: see `bench.program_obs.host_gap_ms`."""
from bench import program_obs


def read(ctx):
    return program_obs.host_gap_ms(ctx)
