"""KV pool: peak blocks leased in the window over the pool's blocks (%)."""


def read(run):
    return 100.0 * run.peak_blocks / run.pool_blocks
