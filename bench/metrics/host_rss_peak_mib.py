"""Peak resident memory of the process (``ru_maxrss``), in MiB."""


def read(ctx):
    return ctx.rss_peak_bytes / 2**20
