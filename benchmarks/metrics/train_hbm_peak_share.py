"""Peak bytes in use on the fullest chip over its memory."""


def read(ctx):
    if ctx.peaks is None or not ctx.device["memory_peak_bytes"]:
        return None
    return 100.0 * ctx.device["memory_peak_bytes"] / ctx.peaks["hbm_bytes"]
