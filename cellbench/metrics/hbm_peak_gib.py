"""Peak device memory of the chip (GiB), read after the window and before
any reference work: it sets how many chips a corpus needs."""


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return peak / float(1 << 30) if peak else None
