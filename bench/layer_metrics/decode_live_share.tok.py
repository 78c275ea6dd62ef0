"""Per-layer metric `decode_live_share.tok`: see
`bench.program_obs.decode_live_share`."""
from bench import program_obs


def read(ctx):
    return program_obs.decode_live_share(ctx)
