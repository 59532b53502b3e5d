"""Peak bytes on the fullest of the cell's devices, GB (1e9 bytes):
the harness's ``memory_peak_bytes`` (lib/device.peak_bytes: the
allocator's peak of buffers plus the runtime's peak reservation for
running programs' temporaries, both from ``memory_stats()``)."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
