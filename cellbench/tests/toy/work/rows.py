"""The toy's work model: a lookup needs each row read once and written
once, and no arithmetic."""


def step_flops(batch, cfg, backward):
    return 0


def step_bytes(batch, cfg, peak, backward):
    return 2 * batch * cfg["row_dim"] * 4


def least_step_seconds(batch, cfg, peak, backward):
    return step_bytes(batch, cfg, peak, backward) / peak["hbm_bytes_per_s"], \
        "bytes"
