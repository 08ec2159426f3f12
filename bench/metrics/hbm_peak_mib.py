"""Peak device memory in use over the run, on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``), in MiB."""


def read(ctx):
    return ctx.memory_peak_bytes / 2**20
