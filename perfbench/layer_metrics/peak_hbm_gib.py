"""Peak device memory after the window, on the fullest chip."""


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
